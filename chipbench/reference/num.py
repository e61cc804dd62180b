"""The reference's number type, and the ``math`` functions it uses.

Every module of the reference imports this in place of :mod:`math`.  By
default reals are Python floats (IEEE float64) and each function is the
standard library's, so the reference computes exactly as the scalar model
it was copied from.  :func:`precision` switches the type for the control
of the ``correct`` comparison: under ``precision(np.float32)`` every
transcendental result, every density and every architecture constant is
held in float32, and NumPy's promotion keeps each operation that touches
one of them in float32.
"""
from __future__ import annotations

import contextlib
import math

inf = math.inf
ceil = math.ceil
floor = math.floor
isqrt = math.isqrt
prod = math.prod
isinf = math.isinf

#: the type every real of the reference is held in
real = float


def lgamma(x):
    return real(math.lgamma(x))


def exp(x):
    return real(math.exp(x))


def log(x):
    return real(math.log(x))


def sqrt(x):
    return real(math.sqrt(x))


def hold_reals(obj, fields) -> None:
    """Cast the named fields of a frozen dataclass to :data:`real`."""
    for f in fields:
        object.__setattr__(obj, f, real(getattr(obj, f)))


@contextlib.contextmanager
def precision(kind):
    """Hold the reference's reals in ``kind`` (e.g. ``numpy.float32``)
    for the duration of the block; build designs and workloads inside
    it, since their constants are cast when they are made."""
    global real
    saved, real = real, kind
    try:
        yield
    finally:
        real = saved
