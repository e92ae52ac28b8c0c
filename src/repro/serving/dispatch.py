"""Packed device programs + host/device overlap for the serving tier
(DESIGN.md §Serving).

Three pieces:

  * ``make_class_advance_fn`` builds THE packed-segment program for a
    *shape class* — the set of workload members whose requests share one
    compiled ``jit(vmap(...))``.  Slot state is stored flat (one padded
    uint32 vector per slot) and each slot carries a member index; inside
    the vmap a ``lax.switch`` over the class's member table reshapes the
    slot's vector into that member's state layout and runs its engine —
    so a mixed ising+gmm burst fills ONE program's slot axis instead of
    round-robining one program per workload group.  Per-slot *traced*
    ``step0`` offsets keep every request on its solo stream.  With a
    ``mesh``, the slot axis is sharded via the standard "chains"
    sharding rule (slots, like chains, never communicate — the sharded
    program is collective-free and bit-identical).
  * ``make_pallas_advance_fn`` is the pallas-execution edition: it
    calls the engine's Pallas executor for the rule once over all slots,
    folded with the engine's §Chains-axis fold helpers, slots in place
    of chains, with per-slot keys and a per-slot ``step0`` (the kernels
    take the absolute-step base as a runtime operand, so heterogeneous
    slot offsets share one compiled kernel).  It launches no kernel of
    its own.
  * ``SegmentPipeline`` bounds how far host-side finalisation may lag
    the device.  The executor pushes one finalize thunk per segment
    (with all needed device slices already enqueued); the pipeline runs
    the oldest thunk only once more than ``depth`` segments are in
    flight, so the host converts/retires segment k's results while the
    device runs segment k+1.

The carried slot state is **donated** segment-to-segment
(``donate_argnums``), so segment k+1's output reuses segment k's
allocation.  ``poison_donated`` enforces the executor-side contract that
retirement slices are enqueued *before* the next donating call: it
deletes the old carry buffers right after dispatch, so any stale read
raises deterministically instead of silently observing donated memory.
"""

from __future__ import annotations

from collections import deque
from functools import partial

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.distributed.sharding import shard_over_chains
from repro.samplers import RunPlan
from repro.samplers.engine import PALLAS_EXECUTORS, final_logp, parse_collect
from repro.samplers.randomness import chain_key


def poison_donated(*arrays) -> None:
    """Make the donation contract loud: delete the carry buffers that
    were just donated to an advance program.

    On backends that honor donation the inputs are already deleted and
    this is a no-op; on backends that silently copy, the stale values
    would remain readable and a bookkeeping bug (slicing retirement
    payloads *after* the next donating call) could hide indefinitely.
    After this, any read of an old carry reference raises
    RuntimeError deterministically on every backend.
    """
    for a in arrays:
        if a is None:
            continue
        delete = getattr(a, "delete", None)
        is_deleted = getattr(a, "is_deleted", None)
        if delete is None or is_deleted is None:
            continue
        try:
            if not a.is_deleted():
                delete()
        except RuntimeError:  # pragma: no cover - committed/tracer buffers
            pass


def make_class_advance_fn(members, n_pad: int, n_slots: int, mesh=None):
    """The packed-segment program for one *shape class*.

    Returns ``advance(words, logp, keys, step0s, tidx, *, seg, collect)``
    -> ``(samples, words', logp', accept)``, each with a leading slot
    axis and flat padded state vectors of width ``n_pad``.  ``seg``
    (segment length) and ``collect`` are jit-static — a serving run
    touches only a handful of (seg, collect) signatures, and within one
    signature every segment of every member reuses the same trace.

    Slot s dispatches on ``tidx[s]`` via ``lax.switch`` over the class's
    member table: member m's branch unflattens ``words[s, :m.size]``
    into m's state layout and runs ``m.engine.run(keys[s], m.target,
    seg, ..., step0=step0s[s])`` — the exact solo-run call — then
    re-flattens and zero-pads back to ``n_pad``.  The packed batch is
    therefore bit-identical to per-request solo runs regardless of which
    members share the burst.  (Under vmap the switch lowers to a select
    over all branches — each slot pays every member's step math — which
    is the price of a single compiled program per class; single-member
    classes skip the switch entirely.)

    MH members carry (words, logp) across segments (``init_logp`` skips
    the boundary re-evaluation); Gibbs members read only words and
    return the final per-site conditional log-prob in the logp lane.
    Both buffers are donated either way so the slot pool never grows the
    heap.
    """
    members = list(members)

    def make_branch(m):
        size = m.size

        def branch(w_flat, lp_flat, k, s0, *, seg, collect):
            w = w_flat[:size].reshape(m.state_shape)
            kwargs = {}
            if m.carry_logp:
                kwargs["init_logp"] = lp_flat[:size].reshape(m.state_shape)
            res = m.engine.submit(
                RunPlan(
                    target=m.target, n_steps=seg, init_words=w, key=k,
                    step0=s0, collect=collect, **kwargs,
                )
            ).result
            pad = n_pad - size
            samples = res.samples.reshape(res.samples.shape[0], size)
            return (
                jnp.pad(samples, ((0, 0), (0, pad))),
                jnp.pad(res.final_words.reshape(size), (0, pad)),
                jnp.pad(
                    res.final_logp.astype(jnp.float32).reshape(size),
                    (0, pad),
                ),
                jnp.pad(res.accept_count.reshape(size), (0, pad)),
            )

        return branch

    branches = [make_branch(m) for m in members]

    @partial(
        jax.jit, static_argnames=("seg", "collect"), donate_argnums=(0, 1)
    )
    def advance(words, logp, keys, step0s, tidx, *, seg, collect):
        bound = [
            partial(b, seg=seg, collect=collect) for b in branches
        ]

        def one(w, lp, k, s0, ti):
            if len(bound) == 1:
                return bound[0](w, lp, k, s0)
            return jax.lax.switch(ti, bound, w, lp, k, s0)

        body = jax.vmap(one)
        body = shard_over_chains(body, mesh, n_slots) or body
        return body(words, logp, keys, step0s, tidx)

    return advance


def make_pallas_advance_fn(engine, target, state_shape: tuple):
    """The packed pallas-segment program: all slots advance in ONE call
    of the engine's Pallas executor for the rule (``run_pallas_mh`` /
    ``run_pallas_gibbs``), with slots in place of chains.

    Returns ``advance(words, keys, step0s, *, seg, collect)`` ->
    ``(samples, words', logp', accept)``, each with a leading slot axis
    and the member's *shaped* ``state_shape`` per slot (pallas kernel
    geometry is per workload, so a pallas executor is a single-member
    class).  The advance derives each slot's stream key as a solo run
    does (``chain_key(key, 0)``), folds the slots with the rule's
    §Chains-axis fold, runs the executor with one chunk of ``seg`` steps
    (one kernel call a segment) and the per-slot absolute-step bases
    ``step0s`` — a runtime operand, so heterogeneous slot offsets
    (mid-flight joins) share one compiled program and every slot
    advances on its solo stream bit-for-bit — and unfolds.  ``collect``
    is "all" or "last", the two modes a per-slot base allows.

    ``words`` is donated; the final log-prob is the engine's
    ``final_logp`` of the final state, so no logp carry crosses
    segments on this path.
    """
    update = engine.config.update
    run_pallas, fold, unfold = PALLAS_EXECUTORS[update]

    @partial(
        jax.jit, static_argnames=("seg", "collect"), donate_argnums=(0,)
    )
    def advance(words, keys, step0s, *, seg, collect):
        s = words.shape[0]
        keys = jax.vmap(lambda k: chain_key(k, 0))(keys)
        samples, acc, state = (
            unfold(x, s) for x in run_pallas(
                engine, keys, target, fold(words), seg,
                step0s.astype(jnp.int32), parse_collect(collect), chunk=seg,
            )
        )
        return samples, state, final_logp(update, target, state), acc

    return advance


class SegmentPipeline:
    """Run host finalize thunks at most ``depth`` segments behind the
    device.  ``push`` defers the thunk; once more than ``depth`` are
    pending the oldest runs (blocking on its device values only then).
    ``drain`` flushes everything — call it when the serve loop idles or
    ends."""

    def __init__(self, depth: int = 2):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = depth
        self._pending: deque = deque()

    def push(self, thunk) -> None:
        self._pending.append(thunk)
        while len(self._pending) > self.depth:
            # backpressure: the host is now > depth segments behind and
            # must block on the oldest segment's device values — the
            # span duration is the donation stall the pipeline absorbed
            with telemetry.span(
                "serving.pipeline_stall", pending=len(self._pending)
            ):
                self._pending.popleft()()

    def drain(self) -> None:
        while self._pending:
            self._pending.popleft()()
