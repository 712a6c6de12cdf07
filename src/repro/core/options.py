"""Unified compile-options API: one frozen dataclass for every knob.

Historically ``compile_graph`` / ``cutpoint.search`` /
``ParallelSearchDriver.search`` each carried their own copy of ~13 loose
keyword knobs, and the three signatures drifted.  :class:`CompileOptions`
is now the single source of truth: every entry point accepts
``options=CompileOptions(...)``, the legacy keyword spellings keep
working through a deprecation shim (:func:`resolve_options`, emitting
:class:`LegacyKnobWarning` -- promoted to an error in tier-1 CI so no
internal caller regresses), and the knob documentation lives in exactly
one place -- the field table below.

The class also draws the line the compile *service* (``repro.service``)
keys its persistent plan cache on: **plan-affecting** fields change what
plan a compile can produce and therefore feed the cache hash
(:meth:`CompileOptions.plan_key`), while **scheduling-only** fields
change wall clock, resilience, or post-checks but never the plan bytes
(:meth:`CompileOptions.schedule`) -- the bit-identity contract proven by
tests/test_search_pool.py, test_score_batch.py, test_alloc_scan.py and
test_branch_bound.py is what makes that split sound.  The same
``plan_key()`` keys the ``resume_dir`` task journals, so journals
written under different plan-affecting option sets can never collide
(they used to: the PR 6 journal key predated ``prune``/``count_pruned``).

Field reference (the one knob table; README mirrors it)
-------------------------------------------------------

Plan-affecting (feed ``plan_key()`` and the service cache hash):

``objective``
    What the optimizer minimizes; feasibility always dominates.
    ``"latency"`` -> (infeasible, latency_cycles, sram_total),
    ``"sram"`` -> (infeasible, sram_total, latency_cycles),
    ``"dram"`` -> (infeasible, dram_total, latency_cycles).
``exhaustive_limit``
    Cut-product spaces up to this size are enumerated exhaustively
    (guaranteed optimum); beyond it coordinate descent with
    deterministic restarts runs instead.  Changing the limit can move a
    graph across that boundary and change the argmin, so it is
    plan-affecting.
``backend``
    ``CutpointEngine`` scoring backend: ``"numpy"`` (default,
    oracle-exact) or ``"pallas"`` (staged float32 on-device batch
    reduction, kernels/score_batch.py -- NOT oracle-exact, hence
    plan-affecting).
``prune``
    ``True`` (default) runs exhaustive enumeration as exact
    branch-and-bound; the argmin and metrics are bit-identical to the
    unpruned search, but ``SearchResult.pruned`` and (under
    ``count_pruned=False``) the scored count depend on it, so compiles
    under different ``prune`` settings must not share journals or cache
    records.
``count_pruned``
    ``True`` (default) counts pruned candidates into
    ``SearchResult.evaluated`` (== the full enumeration count,
    deterministic); ``False`` reports only actually-scored candidates,
    which legitimately varies with scheduling.

Scheduling-only (wall clock / resilience / post-checks; excluded from
``plan_key()`` because results are bit-identical across them):

``workers``
    ``1`` (default) searches serially in-process; ``N > 1`` farms
    disjoint sub-spaces over a process pool
    (``core/search_pool.py``); ``None`` uses ``os.cpu_count()``.  Only
    host engines fan out: an engine that runs jax stays in the calling
    process, which holds the device.
``batch_size``
    Cut tuples priced per ``CutpointEngine.score_batch`` call
    (``1`` falls back to the per-tuple loop).  An ``@N`` suffix on
    ``engine`` overrides it.
``engine``
    How candidate metrics are *executed* (never *what* they are --
    every engine value is bit-identical, which is exactly why the knob
    is scheduling-only).  Grammar: ``name[:variant][@batch]``:

    * ``"journal"`` (default) -- checkpointed Python allocator replay
      per candidate (``CutpointEngine._replay``).
    * ``"device"`` -- tensorized allocator scan over the whole batch
      (``kernels/alloc_scan.py``); variants select the scan
      implementation: ``"device"`` == ``"device:reference"`` (numpy),
      ``"device:scan"`` (``jax.lax.scan``), ``"device:pallas"``.
    * ``"pipeline"`` -- the fully fused on-device search pipeline
      (``kernels/search_pipeline.py``): in-kernel candidate
      enumeration + alloc-scan replay + cost reductions + hierarchical
      argmin; the host receives only each sub-space's winner.
      Variants: ``"pipeline"`` (auto: lax when jax is available, else
      the numpy reference), ``"pipeline:reference"``,
      ``"pipeline:lax"``, ``"pipeline:pallas"``.

    ``@N`` appended to any spelling overrides ``batch_size`` for that
    engine (``"pipeline@4096"``).  The float32 Pallas *scoring* kernel
    is NOT an engine value: it changes plan bytes, so it stays on the
    plan-affecting ``backend`` field.
``max_retries``
    Re-dispatch budget per parallel task for *transient* failures (a
    dead worker process, an injected ChaosError, a straggler
    duplicate).  Deterministic errors always propagate.
``task_deadline_s``
    Per-task wall-clock deadline enabling speculative straggler
    re-dispatch (``None`` disables).
``resume_dir``
    Directory for the task-granular completion journal
    (``checkpoint/checkpoint.py::TaskJournal``): completed tasks are
    committed atomically and skipped on re-run, so a killed or
    preempted compile resumes byte-identically.  The journal's search
    key derives from ``plan_key()`` + the partition, never from
    scheduling knobs.
``verify``
    Static plan verifier (``repro.analysis``) post-pass: ``"off"``
    (default), ``"warn"`` (diagnostics recorded on
    ``plan.diagnostics`` + UserWarning per error), ``"strict"``
    (raises ``VerificationError``).  A pure check -- the plan bytes
    are unchanged -- so the service re-runs it on cache hits instead
    of keying the cache on it.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from dataclasses import dataclass

# Cut-product spaces up to this size are enumerated exhaustively; the
# yolov2 detector's full 7.96M-tuple space fits (paper-scale exactness).
EXHAUSTIVE_LIMIT = 8_000_000

# Cut tuples scored per ``CutpointEngine.score_batch`` call in the search
# loops.  Large enough to amortize the numpy dispatch overhead of the 2-D
# reductions across the batch, small enough that the B x G mask/IO
# matrices stay cache-resident.
DEFAULT_BATCH_SIZE = 1024

_OBJECTIVES = ("latency", "sram", "dram")
_BACKENDS = ("numpy", "pallas")
_VERIFY_MODES = ("off", "warn", "strict")

# engine= grammar: name[:variant][@batch].  Variant "" means the engine's
# default implementation; every (name, variant) pair below is bit-identical
# to every other, which is what keeps ``engine`` scheduling-only.
_ENGINE_VARIANTS = {
    "journal": ("",),
    "device": ("", "reference", "scan", "pallas"),
    "pipeline": ("", "reference", "lax", "pallas"),
}

# The plan-affecting / scheduling-only split (see module docstring).
PLAN_FIELDS = ("objective", "exhaustive_limit", "backend", "prune",
               "count_pruned")
SCHEDULE_FIELDS = ("workers", "batch_size", "engine", "max_retries",
                   "task_deadline_s", "resume_dir", "verify")


class LegacyKnobWarning(DeprecationWarning):
    """A compile entry point was called with loose legacy keyword knobs
    (``workers=``, ``batch_size=``, ``replay=``, ...) instead of
    ``options=CompileOptions(...)``.  The shim maps them onto the
    dataclass so behaviour is unchanged; tier-1 CI promotes this warning
    to an error so no internal caller regresses to the old spelling."""


@dataclass(frozen=True)
class EngineSpec:
    """A parsed ``engine=`` value (see the module docstring's grammar).

    ``variant`` is the resolved implementation name, never ``""``:
    ``resolve_engine`` substitutes each engine's default.  ``batch_size``
    is the effective batch (an ``@N`` suffix wins over the caller's
    default)."""
    name: str                  # "journal" / "device" / "pipeline"
    variant: str               # resolved implementation, e.g. "reference"
    batch_size: int | None     # from "@N", else the caller's default

    def spelling(self) -> str:
        """The canonical string this spec round-trips to."""
        s = f"{self.name}:{self.variant}" if self.name != "journal" \
            else self.name
        if self.batch_size is not None:
            s += f"@{self.batch_size}"
        return s


def _default_variant(name: str) -> str:
    if name == "device":
        return "reference"
    if name == "pipeline":
        # lax is the production default when jax is importable; the numpy
        # reference otherwise.  Both are bit-identical, so auto-selection
        # cannot change results -- only wall clock.
        try:
            import jax                                   # noqa: F401
            return "lax"
        except Exception:                    # pragma: no cover - jax baked
            return "reference"
    return ""


def resolve_engine(engine: str,
                   default_batch: int | None = None) -> EngineSpec:
    """Parse and validate an ``engine=`` string into an :class:`EngineSpec`.

    Raises ``ValueError`` on an unknown name, an unknown variant for the
    name, or a malformed ``@batch`` suffix.  ``default_batch`` fills
    ``batch_size`` when no ``@N`` suffix is present.
    """
    if not isinstance(engine, str):
        raise ValueError(f"engine={engine!r}: expected a string "
                         f"'name[:variant][@batch]'")
    spec, batch = engine, default_batch
    if "@" in spec:
        spec, _, bs = spec.partition("@")
        if not bs.isdigit() or int(bs) < 1:
            raise ValueError(f"engine={engine!r}: '@{bs}' batch suffix "
                             f"must be a positive integer")
        batch = int(bs)
    name, _, variant = spec.partition(":")
    variants = _ENGINE_VARIANTS.get(name)
    if variants is None:
        raise ValueError(f"engine={engine!r}: expected one of "
                         f"{tuple(sorted(_ENGINE_VARIANTS))} "
                         f"(grammar: name[:variant][@batch])")
    if variant not in variants:
        raise ValueError(f"engine={engine!r}: unknown variant "
                         f"{variant!r} for {name!r}; expected one of "
                         f"{tuple(v for v in variants if v)}")
    if not variant:
        variant = _default_variant(name)
    return EngineSpec(name=name, variant=variant, batch_size=batch)


def degrade_engine(engine: str) -> str:
    """The safe fallback spelling for ``engine``: the journal replay,
    preserving any explicit ``@batch`` suffix.

    This is the single degrade target the parallel runtime routes
    through -- a failing device or pipeline task, and every speculative
    straggler duplicate, re-runs under the returned engine (bit-identical
    by the replay contract, so degradation only costs wall clock)."""
    spec = resolve_engine(engine)
    if spec.batch_size is not None:
        return f"journal@{spec.batch_size}"
    return "journal"


try:
    from typing import Protocol, runtime_checkable
except ImportError:                          # pragma: no cover - py>=3.10
    Protocol = object

    def runtime_checkable(cls):
        return cls


@runtime_checkable
class ReplayEngine(Protocol):
    """What the search runtime requires of a candidate-scoring engine.

    ``CutpointEngine`` is the one production implementation;
    ``ParallelSearchDriver``'s workers, the serial ``search`` loop and
    the compile service all resolve their ``CompileOptions.engine``
    string into a concrete implementation through this surface (see
    ``CutpointEngine.run_subspace`` for the dispatch).  Every
    implementation must be bit-identical on ``run_subspace``'s winner --
    the contract that keeps ``engine`` scheduling-only."""

    evaluations: int

    def score_batch(self, cuts_batch, memoize: bool = True,
                    skip=None) -> list: ...

    def run_subspace(self, prefix, suffix_dims, objective: str,
                     batch_size: int, incumbent_key=None,
                     prune: bool = True) -> tuple: ...


@dataclass(frozen=True)
class CompileOptions:
    """Every compile/search knob, in one frozen value object.

    See the module docstring for the per-field reference (the single
    source of truth the README table mirrors).  Instances are immutable
    and hashable; derive variants with :meth:`replace`.
    """

    objective: str = "latency"
    exhaustive_limit: int = EXHAUSTIVE_LIMIT
    workers: int | None = 1
    batch_size: int = DEFAULT_BATCH_SIZE
    engine: str = "journal"
    backend: str = "numpy"
    max_retries: int = 2
    task_deadline_s: float | None = None
    resume_dir: str | os.PathLike | None = None
    prune: bool = True
    count_pruned: bool = True
    verify: str = "off"

    def __post_init__(self) -> None:
        if self.objective not in _OBJECTIVES:
            raise ValueError(f"objective={self.objective!r}: expected one "
                             f"of {_OBJECTIVES}")
        resolve_engine(self.engine)       # validates the grammar; raises
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend={self.backend!r}: expected one of "
                             f"{_BACKENDS}")
        if self.verify not in _VERIFY_MODES:
            raise ValueError(f"verify={self.verify!r}: expected one of "
                             f"{_VERIFY_MODES}")
        if self.exhaustive_limit < 0:
            raise ValueError(f"exhaustive_limit={self.exhaustive_limit}: "
                             f"must be >= 0")
        if self.batch_size < 1:
            raise ValueError(f"batch_size={self.batch_size}: must be >= 1")
        if self.workers is not None and self.workers < 1:
            raise ValueError(f"workers={self.workers}: must be >= 1 or "
                             f"None (= all cores)")
        if self.max_retries < 0:
            raise ValueError(f"max_retries={self.max_retries}: must be "
                             f">= 0")
        if self.task_deadline_s is not None and self.task_deadline_s <= 0:
            raise ValueError(f"task_deadline_s={self.task_deadline_s}: "
                             f"must be > 0 or None")

    # ---------------------------------------------------------- derivation
    def replace(self, **changes) -> "CompileOptions":
        """A copy with ``changes`` applied (re-validated)."""
        return dataclasses.replace(self, **changes)

    def engine_spec(self) -> EngineSpec:
        """The parsed :class:`EngineSpec` of this option set; its
        ``batch_size`` is the effective one (an ``@N`` engine suffix
        overrides the ``batch_size`` field)."""
        return resolve_engine(self.engine, self.batch_size)

    def plan_key(self) -> tuple:
        """Canonical tuple of the plan-affecting fields.

        This is what the service's persistent plan cache and the
        ``resume_dir`` task journals hash: two option sets with equal
        ``plan_key()`` are guaranteed (by the repo's bit-identity
        contract) to compile any request to byte-identical plans, and
        two with different ``plan_key()`` must never share cache records
        or journals.
        """
        return tuple((name, getattr(self, name)) for name in PLAN_FIELDS)

    def schedule(self) -> tuple:
        """Canonical tuple of the scheduling-only fields (wall clock /
        resilience / post-checks; never part of any cache or journal
        key).  ``resume_dir`` is normalized to a string so the tuple
        stays comparable and msgpack-able."""
        out = []
        for name in SCHEDULE_FIELDS:
            v = getattr(self, name)
            if name == "resume_dir" and v is not None:
                v = os.fspath(v)
            out.append((name, v))
        return tuple(out)


_FIELD_NAMES = tuple(f.name for f in dataclasses.fields(CompileOptions))

# Retired keyword spellings the legacy shim still understands.  ``replay``
# predates the unified ``engine`` knob; its two values map 1:1 onto engine
# spellings ("journal" -> "journal", "device" -> "device").
_RETIRED_KNOBS = ("replay",)


def resolve_options(options: CompileOptions | None,
                    legacy: dict | None,
                    site: str = "compile",
                    stacklevel: int = 3) -> CompileOptions:
    """Resolve an entry point's ``(options=, **legacy)`` pair.

    * both empty -> default :class:`CompileOptions`;
    * ``options`` given -> returned as-is (legacy knobs must be absent);
    * legacy knobs given -> mapped onto a fresh ``CompileOptions`` with a
      :class:`LegacyKnobWarning` (promoted to an error in tier-1 CI).
      The retired ``replay=`` spelling is translated onto ``engine=``
      (``"journal"``/``"device"``, unchanged meaning).

    Unknown legacy names raise ``TypeError`` exactly as a wrong keyword
    argument would have before the redesign.
    """
    legacy = dict(legacy) if legacy else {}
    unknown = sorted(set(legacy) - set(_FIELD_NAMES) - set(_RETIRED_KNOBS))
    if unknown:
        raise TypeError(f"{site}() got unexpected keyword argument(s) "
                        f"{', '.join(map(repr, unknown))}")
    if "replay" in legacy:
        if "engine" in legacy:
            raise TypeError(f"{site}(): pass engine=..., not both the "
                            f"retired replay= spelling and engine=")
        legacy["engine"] = legacy.pop("replay")
    if options is not None:
        if not isinstance(options, CompileOptions):
            raise TypeError(f"{site}(): options must be a CompileOptions, "
                            f"got {type(options).__name__}")
        if legacy:
            raise TypeError(
                f"{site}(): pass either options=CompileOptions(...) or "
                f"legacy keyword knobs, not both "
                f"(got {sorted(legacy)})")
        return options
    if legacy:
        warnings.warn(
            f"{site}({', '.join(sorted(legacy))}=...): loose keyword "
            f"knobs are deprecated; pass "
            f"options=CompileOptions({', '.join(sorted(legacy))}=...) "
            f"instead (see repro.core.options)",
            LegacyKnobWarning, stacklevel=stacklevel)
        return CompileOptions(**legacy)
    return CompileOptions()
