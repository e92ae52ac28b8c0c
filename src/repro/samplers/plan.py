"""The run surface: ``RunPlan`` in, ``RunHandle`` out (DESIGN.md §Run-API).

``engine.run`` grew organically — seven positional/keyword arguments, a
carried ``(step0, words, logp)`` resume triple that three subsystems
(tempering, serving, checkpointing) each re-threaded by hand, and a
separate jitted twin (``run_engine``).  ``RunPlan`` collapses that into
one validated spec:

  * **what to sample** — ``target``, ``n_steps``, ``collect``;
  * **which stream**  — ``key`` *or* ``seed`` (exactly one), ``chain_id``;
  * **where to run**  — ``mesh`` (the engine's "chains" sharding rule);
  * **the resume carry** — ``step0`` + ``init_words`` + optional
    ``init_logp``: the exact state a previous segment handed back, so a
    plan *is* a resumable description of the remaining work.

``MHEngine.submit(plan)`` validates the spec against the engine's
config and runs it; the returned ``RunHandle`` carries the result plus
the plan that produced it, and ``handle.resume(n)`` derives the
continuation plan (``step0`` advanced, ``init_words``/``init_logp``
carried) whose stream is bit-identical to one unsegmented run — the
engine's segment-invariance contract (DESIGN.md §Tempering) surfaced as
an object instead of a calling convention.

Everything here is traceable: plans may hold traced arrays (the serving
tier builds plans with traced ``step0`` inside its vmapped segment
program), and validation only inspects python-level structure.  The
``compiled=True`` path routes through a cached jitted dispatcher — the
one-dispatch entry that replaced ``run_engine`` (now a deprecated shim).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from functools import partial
from typing import Any

import jax

from repro import telemetry
from repro.samplers.engine import (
    EngineResult,
    MHEngine,
    _host_side,
    parse_collect,
    resolve_execution,
)


def fingerprint_digest(fingerprint: dict) -> str:
    """A short stable identity of a :meth:`RunPlan.fingerprint` dict —
    what the telemetry log lines print so killed-run forensics can match
    checkpoints to runs without dumping the whole key."""
    blob = json.dumps(fingerprint, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def carries_logp(engine: "MHEngine", target) -> bool:
    """Whether ``engine`` accepts a previous segment's ``final_logp`` as
    the next segment's ``init_logp`` — the solo MH scan carry
    (engine.run's contract).  Everywhere else resume passes ``None`` and
    the engine re-derives the log-prob from the state; ``target.log_prob``
    is pure and deterministic, so the re-evaluation is bit-identical and
    resume stays exact either way."""
    cfg = engine.config
    if cfg.update != "mh" or cfg.num_chains != 1:
        return False
    try:
        return resolve_execution(cfg.execution, target) == "scan"
    except ValueError:
        return False


def _is_concrete_int(x) -> bool:
    """True for python ints (and numpy scalars) — not tracers/arrays."""
    if isinstance(x, jax.core.Tracer):
        return False
    try:
        int(x)
        return True
    except (TypeError, ValueError):
        return False


@dataclasses.dataclass(frozen=True)
class RunPlan:
    """One validated run spec (DESIGN.md §Run-API).

    ``key`` and ``seed`` are mutually exclusive ways to name the
    randomness stream: pass a PRNG key directly, or a python int seed
    that resolves to ``jax.random.PRNGKey(seed)`` at submit time (the
    serving tier's request convention).  ``init_words`` is required —
    the engine never guesses chain state.  ``step0``/``init_logp`` are
    the resume carry; leave them at their defaults for a fresh run.

    Plans are frozen: derive variants with :meth:`replace` (a
    ``dataclasses.replace`` that re-validates).
    """

    target: Any
    n_steps: int
    init_words: Any
    key: Any = None
    seed: int | None = None
    chain_id: int = 0
    step0: Any = 0
    collect: str | None = None
    mesh: Any = None
    init_logp: Any = None

    def __post_init__(self):
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")
        if (self.key is None) == (self.seed is None):
            raise ValueError(
                "a RunPlan names its randomness stream with exactly one of "
                "key= (a PRNG key) or seed= (an int resolved to "
                f"jax.random.PRNGKey at submit); got key={self.key!r}, "
                f"seed={self.seed!r}"
            )
        if self.init_words is None:
            raise ValueError(
                "init_words is required — the engine never guesses chain "
                "state (build it from a workload builder or a previous "
                "handle's final_words)"
            )
        if _is_concrete_int(self.step0) and int(self.step0) < 0:
            raise ValueError(f"step0 must be >= 0, got {self.step0}")
        if self.collect is not None:
            parse_collect(self.collect)

    # -- derivation -----------------------------------------------------
    def replace(self, **updates) -> "RunPlan":
        """A re-validated copy with ``updates`` applied."""
        return dataclasses.replace(self, **updates)

    def resolved_key(self):
        """The PRNG key this plan streams from."""
        if self.key is not None:
            return self.key
        return jax.random.PRNGKey(self.seed)

    @property
    def concrete_step0(self) -> int:
        """``step0`` as a python int (raises on traced offsets)."""
        if not _is_concrete_int(self.step0):
            raise ValueError(
                "this plan carries a traced step0 — only plans with "
                "concrete offsets have a python-level progress"
            )
        return int(self.step0)

    def fingerprint(self, engine: MHEngine) -> dict:
        """A JSON-able identity of (engine axes, stream, state layout) —
        what must match for a checkpointed resume to continue the same
        chain (checkpoint/resume.py).  Deliberately excludes
        ``chunk_steps``/``block_c``/``execution``: chunking and executor
        choice never change the stream (DESIGN.md §2), so a run may be
        resumed under a differently *tuned* engine bit-exactly.
        """
        cfg = engine.config
        key = self.resolved_key()
        try:  # typed key arrays (jax_enable_custom_prng) vs raw uint32
            key = jax.random.key_data(key)
        except (TypeError, ValueError):
            pass
        words = self.init_words
        return {
            "update": cfg.update,
            "randomness": cfg.randomness,
            "p_bfr": cfg.p_bfr,
            "rng_p_bfr": cfg.rng_p_bfr,
            "rng_bit_width": cfg.rng_bit_width,
            "rng_stages": cfg.rng_stages,
            "num_chains": cfg.num_chains,
            "chain_id": int(self.chain_id),
            "collect": self.collect if self.collect is not None else cfg.collect,
            "key": [int(w) for w in list(jax.numpy.ravel(key))],
            "target": type(self.target).__name__,
            "state_shape": [int(s) for s in jax.numpy.shape(words)],
        }


@dataclasses.dataclass
class RunHandle:
    """A finished (segment of a) run: the result, the plan that produced
    it, and the engine it ran on — enough to continue, re-submit, or
    checkpoint it.

    ``resume(n)`` submits the continuation plan: ``step0`` advanced past
    this segment, ``init_words``/``init_logp`` carried from the final
    state, same stream key — so the concatenation of segment sample
    streams is bit-identical to one unsegmented run of the total length.
    """

    plan: RunPlan
    result: EngineResult
    engine: MHEngine

    # result fields, delegated — a handle quacks like an EngineResult
    @property
    def samples(self):
        return self.result.samples

    @property
    def accept_count(self):
        return self.result.accept_count

    @property
    def acceptance_rate(self):
        return self.result.acceptance_rate

    @property
    def final_words(self):
        return self.result.final_words

    @property
    def final_logp(self):
        return self.result.final_logp

    @property
    def n_steps(self):
        return self.result.n_steps

    @property
    def progress(self) -> int:
        """Absolute step after this segment (= the next plan's step0)."""
        return self.plan.concrete_step0 + int(self.plan.n_steps)

    def _carries_logp(self) -> bool:
        """Whether the engine accepts this run's final_logp as the next
        segment's ``init_logp`` (solo MH scan only — engine.run's
        contract)."""
        return carries_logp(self.engine, self.plan.target)

    def resume_plan(self, n_steps: int, **overrides) -> RunPlan:
        """The continuation plan for ``n_steps`` more steps."""
        updates = dict(
            n_steps=n_steps,
            step0=self.progress,
            init_words=self.final_words,
            init_logp=self.final_logp if self._carries_logp() else None,
        )
        updates.update(overrides)
        return self.plan.replace(**updates)

    def resume(self, n_steps: int, **overrides) -> "RunHandle":
        """Run ``n_steps`` more on the same engine (bit-identical to the
        corresponding span of one unsegmented run)."""
        return self.engine.submit(self.resume_plan(n_steps, **overrides))

    def save(self, directory: str) -> str:
        """Checkpoint the resume carry (words/logp/accept) at this
        handle's absolute step via ``repro.checkpoint`` — the durable
        twin of :meth:`resume_plan` (see checkpoint/resume.py for the
        full segment-loop driver).  Emits a structured
        ``run_handle.save`` telemetry log line (fingerprint digest, step,
        path) so killed-run forensics can match the checkpoint to its
        run without re-running anything."""
        from repro.checkpoint import save_checkpoint  # lazy: no cycle

        fingerprint = self.plan.fingerprint(self.engine)
        with telemetry.span("checkpoint.handle_save", step=self.progress):
            path = save_checkpoint(
                directory,
                self.progress,
                {
                    "words": self.final_words,
                    "logp": self.final_logp,
                    "acc": self.accept_count,
                },
                extra={"fingerprint": fingerprint},
            )
        telemetry.log(
            "run_handle.save",
            fingerprint=fingerprint_digest(fingerprint),
            step=self.progress,
            n_steps=int(self.plan.n_steps),
            path=path,
        )
        return path


# --- the one-dispatch compiled entry ---------------------------------------
#
# ``engine``/``target``/``mesh`` are identity-hashed statics (reuse the same
# instances to reuse the trace) — the same contract the deprecated
# ``run_engine`` had, plus mesh support.  Two dispatchers because jit
# operands cannot be optionally-None.


@partial(
    jax.jit,
    static_argnames=(
        "engine", "target", "n_steps", "chain_id", "step0", "collect", "mesh"
    ),
)
def _submit_compiled(
    key, init_words, *, engine, target, n_steps, chain_id, step0, collect,
    mesh,
):
    return engine.run(
        key, target, n_steps, init_words, chain_id=chain_id, mesh=mesh,
        step0=step0, collect=collect,
    )


@partial(
    jax.jit,
    static_argnames=(
        "engine", "target", "n_steps", "chain_id", "step0", "collect", "mesh"
    ),
)
def _submit_compiled_logp(
    key, init_words, init_logp, *, engine, target, n_steps, chain_id, step0,
    collect, mesh,
):
    return engine.run(
        key, target, n_steps, init_words, chain_id=chain_id, mesh=mesh,
        step0=step0, collect=collect, init_logp=init_logp,
    )


def _submit_span(engine: MHEngine, plan: RunPlan, compiled: bool):
    """The ``engine.submit`` telemetry span (DESIGN.md §Telemetry).
    Host-side calls only — inside a jax trace the span would time trace
    construction, not a dispatch, so traced re-entries skip it."""
    cfg = engine.config
    return telemetry.span(
        "engine.submit",
        update=cfg.update,
        randomness=cfg.randomness,
        execution=cfg.execution,
        n_steps=int(plan.n_steps),
        step0=int(plan.step0) if _is_concrete_int(plan.step0) else None,
        collect=plan.collect if plan.collect is not None else cfg.collect,
        num_chains=cfg.num_chains,
        compiled=compiled,
    )


def submit(engine: MHEngine, plan: RunPlan, *, compiled: bool = False):
    """Run ``plan`` on ``engine``; the function behind ``MHEngine.submit``.

    ``compiled=True`` routes through the cached jitted dispatcher (one
    device dispatch; pallas chunk loops collapse in-place — the old
    ``run_engine`` behaviour).  It needs a concrete ``step0``: per-offset
    statics would otherwise recompile per segment, which is exactly the
    trap the serving tier's traced-offset program avoids — so traced
    offsets always take the direct (still traceable) path.

    Telemetry (DESIGN.md §Telemetry): every *host-side* submit runs
    under an ``engine.submit`` span; on the compiled path the span's
    ``jit_cache`` metadata records whether this dispatch compiled
    (``"miss"``) or reused a trace (``"hit"``) — the compile-vs-execute
    split the bench harness aggregates.  Instrumentation is wall-clock
    bookkeeping around the unchanged dispatch calls, so the sampled
    stream is bit-identical with telemetry on or off
    (tests/test_telemetry.py).
    """
    if not isinstance(plan, RunPlan):
        raise TypeError(
            f"submit takes a RunPlan, got {type(plan).__name__} — build one "
            "with samplers.RunPlan(target=..., n_steps=..., init_words=..., "
            "seed=...)"
        )
    key = plan.resolved_key()
    traced = telemetry.enabled() and _host_side()
    span = _submit_span(engine, plan, compiled) if traced else None
    if compiled and _is_concrete_int(plan.step0):
        kw = dict(
            engine=engine,
            target=plan.target,
            n_steps=int(plan.n_steps),
            chain_id=int(plan.chain_id),
            step0=int(plan.step0),
            collect=plan.collect,
            mesh=plan.mesh,
        )
        dispatcher = (
            _submit_compiled if plan.init_logp is None
            else _submit_compiled_logp
        )
        args = (
            (key, plan.init_words) if plan.init_logp is None
            else (key, plan.init_words, plan.init_logp)
        )
        if span is None:
            result = dispatcher(*args, **kw)
        else:
            with span as sp:
                # the trace-cache entry count grows iff this call compiled
                before = dispatcher._cache_size()
                result = dispatcher(*args, **kw)
                after = dispatcher._cache_size()
                sp.set(jit_cache="miss" if after > before else "hit")
    else:
        run_args = (key, plan.target, plan.n_steps, plan.init_words)
        run_kw = dict(
            chain_id=plan.chain_id,
            mesh=plan.mesh,
            step0=plan.step0,
            collect=plan.collect,
            init_logp=plan.init_logp,
        )
        if span is None:
            result = engine.run(*run_args, **run_kw)
        else:
            with span:
                result = engine.run(*run_args, **run_kw)
    return RunHandle(plan=plan, result=result, engine=engine)
