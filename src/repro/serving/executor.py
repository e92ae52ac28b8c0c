"""The packed batch program (DESIGN.md §Serving).

``PackedExecutor`` owns ``n_slots`` request slots and advances them in
lock-step ``chunk_steps`` segments.  Admission and retirement happen
only **between** chunks, and the packed batch is bit-identical to solo
runs because each slot replays exactly the solo call:

  * slot state is the engine carry with a leading slot axis, donated
    segment-to-segment (serving/dispatch.py) — stored *flat* (one
    zero-padded uint32 vector per slot) under scan execution so
    heterogeneous workload members share the pool, and shaped under
    pallas (kernel geometry is per workload);
  * each slot streams from its *request's* key (``PRNGKey(seed)`` split
    exactly as ``launch.sample`` does), so the stream belongs to the
    request, never to the slot — slot reuse after retirement is safe by
    construction;
  * each slot carries its absolute step as the engine's ``step0`` resume
    offset; both executors take it as a runtime value (the fused pallas
    kernels as a per-slot operand), so slots at different absolute steps
    advance in ONE device program and a request joining mid-flight
    continues the exact stream its solo run would produce.

**Shape classes**: one executor serves every workload member whose
requests can share its compiled advance program.  Under scan execution
the member table is open — ``add_member`` registers another workload
and the class program dispatches per-slot via ``lax.switch``
(dispatch.make_class_advance_fn), so a mixed ising+gmm burst fills one
program's slot axis.  Under pallas execution the executor is a
single-member class (one batched fused-kernel grid over all slots —
dispatch.make_pallas_advance_fn; the historical one-solo-submit-per-slot
fallback is gone).

Per-request collection: the segment program collects ``"all"`` iff any
active request keeps samples (else ``"last"`` — O(state) memory); a
``thin:k`` request then keeps the static strided slice of its slot's
rows on *absolute* steps ``(step0 + t) % k == 0``, bit-identical to the
engine's own ``thin`` stream (DESIGN.md §Collection).

Donation contract: retirement/collection slices MUST be enqueued before
the next donating advance — and the executor *enforces* it by poisoning
the donated carry buffers right after each dispatch
(dispatch.poison_donated), so a stale read raises instead of silently
observing reused memory.  ``advance_compiles`` counts compiled advance
programs (jit-cache growth), the compiled-programs-per-burst number the
serving benchmarks gate on.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry, workloads
from repro.samplers.engine import parse_collect, resolve_execution
from repro.serving import dispatch
from repro.serving.dispatch import SegmentPipeline

_DUMMY_KEY = np.zeros((2,), np.uint32)  # free slots advance discarded work


@dataclasses.dataclass(frozen=True)
class _Member:
    """One workload group inside a shape class: the (engine, target)
    pair plus the request plumbing and this member's slot-state layout.
    ``index`` is the member's branch position in the class program's
    ``lax.switch`` table."""

    name: str
    engine: object
    target: object
    state_shape: tuple
    request_init: object         # req -> (init_words, run_key, n_steps)
    default_steps: int | None
    index: int

    @property
    def size(self) -> int:
        return int(math.prod(self.state_shape))

    @property
    def carry_logp(self) -> bool:
        return self.engine.config.update == "mh"

    @property
    def rate_label(self) -> str:
        return (
            "flip_rate" if self.engine.config.update == "gibbs"
            else "acceptance_rate"
        )


@dataclasses.dataclass
class _Slot:
    """Executor-side bookkeeping for one admitted request."""

    req: object
    member: _Member
    remaining: int               # steps still to run
    mode: str                    # parsed collect mode: all | thin | last
    thin_k: int                  # stride under thin
    progress: int = 0            # absolute step == step0 of the next segment
    pieces: list = dataclasses.field(default_factory=list)  # device kept rows
    acc: object = None           # device per-site accept/flip accumulator
    final_words: object = None
    final_logp: object = None


def _workload_member_parts(
    name: str,
    *,
    randomness: str,
    execution: str,
    smoke: bool,
    **builder_kwargs,
):
    """(engine, target, state_shape, request_init, default_steps) for a
    workload group — engine + target built once (group key 0; for
    seed-dependent targets like spin_glass the group fixes the problem
    instance), requests supply per-request inits and streams.

    ``request_init`` replays the solo-run derivation of ``launch.sample``
    exactly: ``PRNGKey(seed)`` -> split -> (builder init from k_init,
    chain stream from k_run) — so a packed request reproduces
    ``engine.run(k_run, target, n, init)`` bit-for-bit.
    """
    builder = workloads.WORKLOADS[name]
    params = inspect.signature(builder).parameters
    kwargs = {
        k: v
        for k, v in dict(
            randomness=randomness,
            backend=execution,
            smoke=smoke,
            **builder_kwargs,
        ).items()
        if k in params and v is not None
    }
    template = workloads.build(name, jax.random.PRNGKey(0), **kwargs)

    def request_init(req):
        key = jax.random.PRNGKey(req.seed)
        k_init, k_run = jax.random.split(key)
        wl = workloads.build(name, k_init, **kwargs)
        n = req.n_steps if req.n_steps else wl.n_steps
        return wl.init_words, k_run, n

    return (
        template.engine,
        template.target,
        tuple(template.init_words.shape),
        request_init,
        template.n_steps,
    )


class PackedExecutor:
    """``n_slots`` heterogeneous requests packed into one device program.

    Construct via ``for_workload`` (the registry path the scheduler
    uses) or directly with an engine/target pair plus a
    ``request_init(req) -> (init_words, run_key, n_steps)`` callable
    (the hook tests use to pin exact solo references).  Additional
    workload members join a scan-execution executor via
    ``add_workload``/``add_member`` — the shape-class packing axis.
    """

    def __init__(
        self,
        engine,
        target,
        n_slots: int,
        state_shape: tuple,
        *,
        request_init,
        default_steps: int | None = None,
        chunk_steps: int | None = None,
        pipeline_depth: int = 2,
        clock=time.perf_counter,
        workload: str = "default",
        mesh=None,
    ):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self._check_engine(engine)
        self.n_slots = int(n_slots)
        self.chunk_steps = int(chunk_steps or engine.config.chunk_steps)
        self.clock = clock
        self.mesh = mesh
        self.execution = resolve_execution(
            engine.config.execution, target, engine.config.update
        )
        if mesh is not None and self.execution != "scan":
            raise ValueError(
                "mesh-sharded serving shards the slot axis of the scan "
                "class program — pallas execution folds slots into one "
                "kernel grid on a single device (use execution='scan' "
                "with a mesh)"
            )
        self.members: list[_Member] = [
            _Member(
                name=workload, engine=engine, target=target,
                state_shape=tuple(state_shape), request_init=request_init,
                default_steps=default_steps, index=0,
            )
        ]
        self.pipeline = SegmentPipeline(pipeline_depth)
        self.advance_compiles = 0    # compiled advance programs (cache growth)
        self._slots: list[_Slot | None] = [None] * self.n_slots
        self._keys: list = [_DUMMY_KEY] * self.n_slots
        if self.execution == "scan":
            self.n_pad = self.members[0].size
            self.words = jnp.zeros((self.n_slots, self.n_pad), jnp.uint32)
            self.logp = jnp.zeros((self.n_slots, self.n_pad), jnp.float32)
        else:
            self.n_pad = self.members[0].size
            self.words = jnp.zeros(
                (self.n_slots, *self.members[0].state_shape), jnp.uint32
            )
            self.logp = None
        self._rebuild_advance()

    @staticmethod
    def _check_engine(engine) -> None:
        if engine.config.num_chains != 1:
            raise ValueError(
                "the serving tier packs requests into the batch itself — "
                "configure the engine with num_chains=1 (got "
                f"{engine.config.num_chains})"
            )

    def _rebuild_advance(self) -> None:
        if self.execution == "scan":
            self._advance = dispatch.make_class_advance_fn(
                self.members, self.n_pad, self.n_slots, mesh=self.mesh
            )
        else:
            m = self.members[0]
            self._advance = dispatch.make_pallas_advance_fn(
                m.engine, m.target, m.state_shape
            )

    # -- construction from the workload registry -----------------------
    @classmethod
    def for_workload(
        cls,
        name: str,
        *,
        n_slots: int,
        randomness: str = "cim",
        execution: str = "scan",
        smoke: bool = True,
        chunk_steps: int | None = None,
        pipeline_depth: int = 2,
        clock=time.perf_counter,
        mesh=None,
        **builder_kwargs,
    ) -> "PackedExecutor":
        """An executor whose first member is workload ``name`` (see
        ``_workload_member_parts`` for the per-request derivation)."""
        engine, target, shape, request_init, default_steps = (
            _workload_member_parts(
                name, randomness=randomness, execution=execution,
                smoke=smoke, **builder_kwargs,
            )
        )
        return cls(
            engine,
            target,
            n_slots,
            shape,
            request_init=request_init,
            default_steps=default_steps,
            chunk_steps=chunk_steps,
            pipeline_depth=pipeline_depth,
            clock=clock,
            workload=name,
            mesh=mesh,
        )

    # -- shape-class membership ----------------------------------------
    def member_for(self, workload: str | None) -> _Member:
        """The member serving ``workload`` (single-member executors
        accept any name — the direct-construction test path)."""
        if len(self.members) == 1:
            return self.members[0]
        for m in self.members:
            if m.name == workload:
                return m
        raise KeyError(
            f"workload {workload!r} is not a member of this shape class "
            f"({[m.name for m in self.members]})"
        )

    def has_member(self, workload: str) -> bool:
        return any(m.name == workload for m in self.members)

    def add_member(
        self, name, engine, target, state_shape, request_init,
        default_steps=None,
    ) -> _Member:
        """Register another workload group in this shape class (scan
        execution only — pallas kernel geometry is per workload).  Live
        slots keep advancing: the flat pool re-pads in place if the new
        member's state is wider, and the class program is rebuilt with
        the extended ``lax.switch`` table."""
        if self.execution != "scan":
            raise ValueError(
                "pallas executors are single-member shape classes — the "
                "fused kernel grid is specialised to one workload's "
                "state geometry; mixed pallas bursts run one executor "
                "(one program) per workload"
            )
        self._check_engine(engine)
        if resolve_execution(
            engine.config.execution, target, engine.config.update
        ) != "scan":
            raise ValueError(
                "shape-class members must resolve to scan execution"
            )
        if self.has_member(name):
            return self.member_for(name)
        m = _Member(
            name=name, engine=engine, target=target,
            state_shape=tuple(state_shape), request_init=request_init,
            default_steps=default_steps, index=len(self.members),
        )
        self.members.append(m)
        if m.size > self.n_pad:
            grow = m.size - self.n_pad
            self.words = jnp.pad(self.words, ((0, 0), (0, grow)))
            self.logp = jnp.pad(self.logp, ((0, 0), (0, grow)))
            self.n_pad = m.size
        self._rebuild_advance()
        return m

    def add_workload(
        self,
        name: str,
        *,
        randomness: str = "cim",
        execution: str = "scan",
        smoke: bool = True,
        **builder_kwargs,
    ) -> _Member:
        """``add_member`` fed from the workload registry (the scheduler's
        shape-class path)."""
        parts = _workload_member_parts(
            name, randomness=randomness, execution=execution, smoke=smoke,
            **builder_kwargs,
        )
        return self.add_member(name, *parts)

    # -- primary-member views (single-workload API compatibility) ------
    @property
    def engine(self):
        return self.members[0].engine

    @property
    def target(self):
        return self.members[0].target

    @property
    def state_shape(self) -> tuple:
        return self.members[0].state_shape

    @property
    def request_init(self):
        return self.members[0].request_init

    @property
    def default_steps(self):
        return self.members[0].default_steps

    @property
    def rate_label(self) -> str:
        return self.members[0].rate_label

    # -- slot pool ------------------------------------------------------
    def has_free_slot(self) -> bool:
        return any(s is None for s in self._slots)

    @property
    def active_count(self) -> int:
        return sum(s is not None for s in self._slots)

    def admit(self, req) -> int:
        """Place a request in a free slot (between chunks only — callers
        never see a partially-advanced admission)."""
        try:
            slot = next(i for i, s in enumerate(self._slots) if s is None)
        except StopIteration:
            raise RuntimeError("no free slot — check has_free_slot()") from None
        member = self.member_for(getattr(req, "workload", None))
        with telemetry.span(
            "serving.admit", workload=getattr(req, "workload", None),
            slot=slot,
        ):
            init, k_run, n_steps = member.request_init(req)
            init = jnp.asarray(init)
            if tuple(init.shape) != member.state_shape:
                raise ValueError(
                    f"request init shape {tuple(init.shape)} != member "
                    f"state shape {member.state_shape} — one member "
                    f"serves one workload group"
                )
            mode, k = parse_collect(req.collect)
            words0 = init.astype(jnp.uint32)
            if self.execution == "scan":
                flat = jnp.pad(
                    words0.reshape(-1), (0, self.n_pad - member.size)
                )
                self.words = self.words.at[slot].set(flat)
                if member.carry_logp:
                    lp0 = member.target.log_prob(words0).astype(jnp.float32)
                    self.logp = self.logp.at[slot].set(
                        jnp.pad(
                            lp0.reshape(-1), (0, self.n_pad - member.size)
                        )
                    )
            else:
                self.words = self.words.at[slot].set(words0)
            self._keys[slot] = jnp.asarray(k_run, jnp.uint32)
        self._slots[slot] = _Slot(
            req=req, member=member, remaining=int(n_steps), mode=mode,
            thin_k=k,
        )
        req.slot = slot
        req.rate_label = member.rate_label
        req.t_admit = self.clock()
        return slot

    # -- the chunk loop -------------------------------------------------
    def advance_chunk(self) -> list:
        """Advance every active slot one segment; returns the requests
        that finished (their results materialise when the dispatch
        pipeline flushes — ``drain()`` forces it)."""
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return []
        # the segment never overshoots the shortest remaining budget, so
        # every retirement lands exactly on a chunk boundary
        seg = min(self.chunk_steps, *(self._slots[i].remaining for i in active))
        with telemetry.span(
            "serving.segment",
            seg=seg, active=len(active), execution=self.execution,
        ):
            if self.execution == "scan":
                retired = self._advance_scan(active, seg)
            else:
                retired = self._advance_pallas(active, seg)
        telemetry.counter(
            "serving_segments_total", "packed segments dispatched"
        ).inc(execution=self.execution)
        telemetry.counter(
            "serving_slot_steps_total", "slot-steps advanced"
        ).inc(seg * len(active))
        finished = []
        if retired:
            batch = []
            for i in retired:
                s = self._slots[i]
                self._slots[i] = None          # slot free for the next admit
                self._keys[i] = _DUMMY_KEY
                batch.append(s)
                finished.append(s.req)
            self.pipeline.push(
                lambda fs=batch: self._finalize_batch(fs)
            )
        return finished

    def _segment_inputs(self, active):
        collect = (
            "all"
            if any(self._slots[i].mode != "last" for i in active)
            else "last"
        )
        step0s = jnp.asarray(
            [s.progress if s else 0 for s in self._slots], jnp.int32
        )
        keys = jnp.stack([jnp.asarray(k, jnp.uint32) for k in self._keys])
        return collect, step0s, keys

    def _count_compiles(self, before: int) -> None:
        grew = self._advance._cache_size() - before
        if grew > 0:
            self.advance_compiles += grew
            telemetry.counter(
                "serving_advance_compiles_total",
                "compiled packed advance programs",
            ).inc(grew, execution=self.execution)

    def _advance_scan(self, active, seg: int) -> list:
        """One vmapped class program over all slots: flat donated
        (words, logp) carry, traced per-slot ``step0``, per-slot member
        dispatch (dispatch.make_class_advance_fn)."""
        with telemetry.span("serving.inputs"):
            collect, step0s, keys = self._segment_inputs(active)
            tidx = jnp.asarray(
                [s.member.index if s else 0 for s in self._slots], jnp.int32
            )
        old_words, old_logp = self.words, self.logp
        with telemetry.span("serving.dispatch"):
            before = self._advance._cache_size()
            samples, words, logp, acc = self._advance(
                old_words, old_logp, keys, step0s, tidx, seg=seg,
                collect=collect,
            )
            self._count_compiles(before)
        self.words, self.logp = words, logp

        def rows(i, m):
            return samples[i][:, :m.size].reshape(-1, *m.state_shape)

        def unflat(buf, i, m):
            return buf[i, :m.size].reshape(m.state_shape)

        with telemetry.span("serving.bookkeep"):
            # the donated carries are dead from here on — make stale
            # reads loud
            dispatch.poison_donated(old_words, old_logp)
            return self._bookkeep(
                active, seg, collect, rows,
                lambda i, m: unflat(acc, i, m),
                lambda i, m: unflat(words, i, m),
                lambda i, m: unflat(logp, i, m),
            )

    def _advance_pallas(self, active, seg: int) -> list:
        """One batched fused-kernel grid over all slots: shaped donated
        words carry, per-slot key words and operand ``step0``
        (dispatch.make_pallas_advance_fn).  No per-slot fallback."""
        with telemetry.span("serving.inputs"):
            collect, step0s, keys = self._segment_inputs(active)
        old_words = self.words
        with telemetry.span("serving.dispatch"):
            before = self._advance._cache_size()
            samples, words, logp, acc = self._advance(
                old_words, keys, step0s, seg=seg, collect=collect
            )
            self._count_compiles(before)
        self.words = words
        with telemetry.span("serving.bookkeep"):
            dispatch.poison_donated(old_words)
            return self._bookkeep(
                active, seg, collect,
                lambda i, m: samples[i],
                lambda i, m: acc[i],
                lambda i, m: words[i],
                lambda i, m: logp[i],
            )

    def _bookkeep(
        self, active, seg, collect, rows_of, acc_of, words_of, logp_of
    ) -> list:
        """Per-slot segment bookkeeping: slice retirement/collection
        payloads NOW (the donated inputs are already poisoned — these
        getters read the segment *outputs*), advance progress, collect
        retirees."""
        retired = []
        for i in active:
            s = self._slots[i]
            m = s.member
            if collect == "all" and s.mode != "last":
                r = rows_of(i, m)
                if s.mode == "all":
                    s.pieces.append(r)
                else:  # thin: static strided slice on absolute steps
                    i0 = (-s.progress) % s.thin_k
                    if i0 < seg:
                        s.pieces.append(r[i0::s.thin_k])
            a = acc_of(i, m)
            s.acc = a if s.acc is None else s.acc + a
            s.progress += seg
            s.remaining -= seg
            if s.remaining == 0:
                s.final_words = words_of(i, m)
                s.final_logp = logp_of(i, m)
                retired.append(i)
        return retired

    # -- retirement -----------------------------------------------------
    def _finalize_batch(self, batch: list) -> None:
        """Finalize a batch of retired slots under one span — the span
        duration IS the donation/materialisation stall the pipeline
        deferred (host blocks on device values here)."""
        with telemetry.span("serving.finalize", retired=len(batch)):
            for s in batch:
                self._finalize(s)
        telemetry.counter(
            "serving_requests_retired_total", "requests finalized"
        ).inc(len(batch))
        for s in batch:
            req = s.req
            wl = getattr(req, "workload", "?")
            wait = getattr(req, "wait_s", None)
            if wait is not None:
                telemetry.histogram(
                    "serving_wait_seconds", "arrival -> admission"
                ).observe(wait, workload=wl)
            service = getattr(req, "service_s", None)
            if service is not None:
                telemetry.histogram(
                    "serving_service_seconds", "admission -> materialised"
                ).observe(service, workload=wl)

    def _finalize(self, s: _Slot) -> None:
        """Host-side retirement: materialise the request's payload and
        stamp delivery time.  Runs deferred through the dispatch
        pipeline — by then the device values are usually already done."""
        req = s.req
        if s.pieces:
            req.samples = np.concatenate(
                [np.asarray(p) for p in s.pieces], axis=0
            )
        else:
            req.samples = np.zeros((0, *s.member.state_shape), np.uint32)
        req.final_words = np.asarray(s.final_words)
        req.final_logp = np.asarray(s.final_logp)
        req.accept_count = np.asarray(s.acc)
        total = max(1, s.progress * int(np.prod(s.member.state_shape)))
        req.acceptance_rate = float(req.accept_count.sum()) / total
        req.t_done = self.clock()

    def drain(self) -> None:
        """Flush the deferred finalize pipeline (every retired request's
        result is host-materialised after this returns)."""
        self.pipeline.drain()
