"""Faults planted under a search's timed path, for the tests and for
reading them on the chip at a cell's own size.  Each installs itself
with a ``monkeypatch``-like ``setattr`` and empties the program caches,
so the next search traces its generation loop anew with the fault in
it."""
from __future__ import annotations


def _fresh_programs() -> None:
    from repro.core import batched
    batched.clear_caches()


def stalled_search(setattr_) -> None:
    """Every generation returns the search state (key, population,
    fitness, pending offspring) unchanged; only the archive moves."""
    import jax
    real = jax.lax.scan

    def scan(f, init, xs=None, length=None, **kw):
        if isinstance(init, tuple) and len(init) == 6 and xs is None:
            body = f

            def f(carry, x):
                new, ys = body(carry, x)
                return tuple(carry[:4]) + tuple(new[4:]), ys
        return real(f, init, xs, length=length, **kw)

    setattr_(jax.lax, "scan", scan)
    _fresh_programs()


def half_population(setattr_) -> None:
    """Half of each generation's population is left out: its candidates
    get no answer (invalid, infinite fitness) and the survivors come from
    the rest."""
    import jax
    import jax.numpy as jnp
    real = jax.vmap

    def vmap(fun, *a, **kw):
        out = real(fun, *a, **kw)
        if getattr(fun, "__name__", "") != "_eval_one":
            return out

        def halved(pending, *rest):
            fit, cyc, en, edp, valid, nudged = out(pending, *rest)
            keep = jnp.arange(fit.shape[0]) < fit.shape[0] // 2
            return (jnp.where(keep, fit, jnp.inf), cyc, en, edp,
                    valid & keep, nudged)
        return halved

    setattr_(jax, "vmap", vmap)
    _fresh_programs()


SEARCH_FAULTS = {"stalled_search": stalled_search,
                 "half_population": half_population}
