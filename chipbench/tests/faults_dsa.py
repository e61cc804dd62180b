"""A fault planted under a DeepSeek Sparse Attention sweep's timed path,
for the tests and for reading it on the chip at the cell's own size.
It installs itself with a ``monkeypatch``-like ``setattr``."""
from __future__ import annotations


def selection_dropped(setattr_) -> None:
    """``dsa-skip`` evaluates the selected entries without their
    selection: every cache token kept (k = L), on the option's design."""
    from repro.fleet import sweep
    real = sweep._evaluate_shapes

    def dropped(option, rows, **kw):
        if option.selected_only:
            rows = [(M, K, N, {t: (kind, {**arg, "k": arg["L"]})
                               for t, (kind, arg) in dens.items()})
                    for M, K, N, dens in rows]
        return real(option, rows, **kw)

    setattr_(sweep, "_evaluate_shapes", dropped)


DSA_FAULTS = {"selection_dropped": selection_dropped}
