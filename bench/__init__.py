"""The chip benchmark: cells, traffic, drivers and metric readers as data
named by ``BENCHMARK.json`` (see ``bench/README.md``)."""
