"""What the device produced: the winner row of each fused sub-space search.

A ``pipeline:<variant>`` engine runs each exhaustive sub-space through
``kernels/search_pipeline.py::_run_<variant>``, whose result is the
device's winner row ``(infeasible, primary key, secondary key, linear
index)`` after the host fold of every launch's winner.  The program then
decodes the index and re-prices that one tuple on the host, so the row is
the only thing in a plan that the device's arithmetic decided.
``DeviceRows`` wraps that function while a target lives and keeps each
row with the sub-space it came from, for the comparison with the
reference.  Other engines have no such row (``expects_rows``).
"""
from __future__ import annotations


def _pipeline_variant(engine: str) -> str | None:
    name, _, variant = engine.split("@")[0].partition(":")
    return (variant or "reference") if name == "pipeline" else None


def expects_rows(engine: str) -> bool:
    return _pipeline_variant(engine) is not None


class DeviceRows:
    def __init__(self, engine: str):
        variant = _pipeline_variant(engine)
        self.rows: list = []
        self._undo = None
        if variant is None:
            return
        from repro.kernels import search_pipeline as sp
        attr = f"_run_{variant}"
        orig = getattr(sp, attr)
        rows = self.rows

        def recorded(engine, tbl, prefix, dims, strides, S, chunk,
                     objective):
            best = orig(engine, tbl, prefix, dims, strides, S, chunk,
                        objective)
            rows.append({"prefix": [int(c) for c in prefix],
                         "dims": [int(d) for d in dims],
                         "objective": objective,
                         "row": None if best is None
                         else [float(x) for x in best]})
            return best

        setattr(sp, attr, recorded)
        self._undo = lambda: setattr(sp, attr, orig)

    def take(self) -> list:
        out = list(self.rows)
        self.rows.clear()
        return out

    def close(self) -> None:
        if self._undo is not None:
            self._undo()
            self._undo = None
