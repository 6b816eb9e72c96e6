"""Set-up time: process start to the window's start (loading, building
the system, making the inputs, warming every program the window runs)."""


def read(cell):
    return cell.setup_s
