"""Uniform kernel ABI (paper §5.1).

DPR requires every kernel loaded into an RR to present the *same* external
interface; the paper pads the HLS signature with dummy arguments
(``i_args_<n>``, unused float and pointer args).  Here the same role is
played by ``ArgBundle``: a fixed number of buffer slots plus fixed-width
int/float argument vectors, dummy-padded.  Every region worker therefore has
ONE dispatch path — launching a different kernel never changes the host-side
call structure, only the loaded executable ("bitstream").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

N_BUF_SLOTS = 6    # pointer args (HitTiles); unused slots hold (1,1) dummies
N_INT_ARGS = 8     # the paper pads to 8 integer scalars
N_FLOAT_ARGS = 8   # ... and 8 float scalars


@dataclass
class ArgBundle:
    """Uniform argument record.  ``bufs`` are jax/np arrays (HitTile data);
    ints/floats are padded to fixed width."""
    bufs: Tuple[Any, ...] = ()
    ints: Tuple[int, ...] = ()
    floats: Tuple[float, ...] = ()
    # memoized signature: it is read on every scheduler dispatch/affinity
    # check and prefetch hint, and the shapes never change after creation
    _sig: Optional[tuple] = field(default=None, repr=False, compare=False)
    # memoized padded() result: a preempted/migrated task is re-dispatched
    # many times, and re-padding + re-uploading the scalar vectors on every
    # launch is pure overhead — the bundle is immutable after creation.
    # The buffer slots stay host numpy (the launch path uploads them once
    # and thereafter the payload lives device-resident in the chunk
    # pipeline); the int/float vectors are device arrays reused across
    # dispatches (never donated), memoized per target device.
    _padded: Optional[tuple] = field(default=None, repr=False, compare=False)
    _scalars: dict = field(default_factory=dict, repr=False, compare=False)

    def host(self):
        """``(bufs, ints, floats)`` at the uniform ABI width, as host
        numpy (memoized): nothing is uploaded."""
        if self._padded is None:
            bufs = list(self.bufs)[:N_BUF_SLOTS]
            while len(bufs) < N_BUF_SLOTS:
                bufs.append(np.zeros((1, 1), np.float32))  # dummy pointer arg
            ints = list(self.ints)[:N_INT_ARGS]
            ints += [0] * (N_INT_ARGS - len(ints))
            floats = list(self.floats)[:N_FLOAT_ARGS]
            floats += [0.0] * (N_FLOAT_ARGS - len(floats))
            self._padded = (tuple(bufs), np.asarray(ints, np.int32),
                            np.asarray(floats, np.float32))
        return self._padded

    @property
    def n_dummies(self) -> int:
        """How many of ``host()``'s buffer slots are ``(1, 1)`` dummies
        (the last ones)."""
        return N_BUF_SLOTS - min(len(self.bufs), N_BUF_SLOTS)

    def scalars(self, device=None):
        """The memoized device ``(ints, floats)`` on ``device``, or None
        until an upload there is kept (``keep_scalars``)."""
        return self._scalars.get(device)

    def keep_scalars(self, device, scalars):
        """Memoize ``(ints, floats)`` uploaded to ``device``; returns them."""
        scalars = self._scalars[device] = tuple(scalars)
        return scalars

    def padded(self, device=None):
        """``(bufs, ints, floats)`` at the uniform ABI width, with the
        scalar vectors on ``device`` (``None``: JAX's default device)."""
        bufs, ints, floats = self.host()
        scalars = self.scalars(device) or self.keep_scalars(
            device, jax.device_put((ints, floats), device))
        return (bufs,) + scalars

    def signature(self) -> tuple:
        """Shape/dtype signature — the 'interface' a region must be
        configured for (kernel + signature = one executable)."""
        if self._sig is None:
            bufs, _, _ = self.host()
            self._sig = tuple((tuple(b.shape), buffer_dtype(b).name)
                              for b in bufs)
        return self._sig


def buffer_dtype(b) -> np.dtype:
    """The dtype a buffer has on the device (64-bit host types narrow
    unless x64 is on), read without uploading the buffer."""
    dtype = b.dtype if hasattr(b, "dtype") else np.asarray(b).dtype
    return jax.dtypes.canonicalize_dtype(dtype)


def abi_signature(bundle: ArgBundle) -> tuple:
    return bundle.signature()
