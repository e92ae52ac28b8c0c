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
  * ``make_pallas_advance_fn`` is the pallas-execution edition: all
    slots fold into ONE batched fused-kernel grid (the §Chains-axis
    fold, with per-slot keys and per-slot operand ``step0`` — the fused
    kernels take the absolute-step base as a runtime operand, so
    heterogeneous slot offsets share one compiled kernel).  This
    replaces the historical per-slot solo-submit fallback.
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
from repro.samplers import RunPlan
from repro.samplers.engine import (
    _chains_fold_mh,
    _fused_gibbs_logit,
    _fused_key_cols,
)


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


def _slot_axis_wrap(mesh, n_slots: int, n_in: int, n_out: int):
    """shard_map wrapper over the slot axis, or identity without a mesh.

    Slots resolve through the "chains" sharding rule (they are the same
    kind of axis: independent, never communicating), including the
    divisibility filter — a slot count the mesh doesn't divide runs
    replicated rather than padded.
    """
    if mesh is None:
        return lambda body: body
    from repro.distributed import sharding

    spec = sharding.spec_for(("chains",), shape=(n_slots,), mesh=mesh)
    if spec is None or len(spec) == 0 or spec[0] is None:
        return lambda body: body
    p = jax.sharding.PartitionSpec(spec[0])
    return lambda body: jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(p for _ in range(n_in)),
        out_specs=tuple(p for _ in range(n_out)),
        check_vma=False,
    )


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
    wrap = _slot_axis_wrap(mesh, n_slots, n_in=5, n_out=4)

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

        def body(w, lp, k, s0, ti):
            return jax.vmap(one)(w, lp, k, s0, ti)

        return wrap(body)(words, logp, keys, step0s, tidx)

    return advance


def make_pallas_advance_fn(engine, target, state_shape: tuple):
    """The packed pallas-segment program: one batched fused-kernel grid
    over ALL slots (no per-slot fallback).

    Returns ``advance(words, keys, step0s, *, seg, collect)`` ->
    ``(samples, words', logp', accept)``, each with a leading slot axis
    and the member's *shaped* state (pallas kernel geometry is per
    workload, so a pallas executor is a single-member class).  The fold
    is exactly the §Chains-axis fold with slots in place of chains —
    slot-major into the MH compartment axis (site = i·C + c stays the
    solo site index) or the Gibbs lattice-batch axis (i mod B stays the
    solo lattice index) — and the fused kernels take per-column /
    per-lattice key words AND the absolute-step base ``step0`` as
    runtime operands, so heterogeneous slot offsets (mid-flight joins)
    share one compiled program and every slot advances on its solo
    stream bit-for-bit.  Host/cim randomness ships per-slot operand
    chunks drawn at each slot's own offset instead.

    ``words`` is donated; MH re-derives the final log-prob from the
    table and Gibbs returns the final per-site conditional log-prob, so
    no logp carry crosses segments on this path.
    """
    from repro.samplers.randomness import chain_key

    backend = engine.randomness
    update = engine.config.update
    block_c = engine.config.block_c

    def _slot_chain_keys(keys):
        # engine.run derives every stream from chain_key(key, chain_id=0)
        # before touching the executors — replay that fold per slot so
        # the packed kernels read the exact solo streams
        return jax.vmap(lambda k: chain_key(k, 0))(keys)

    if update == "mh":
        from repro.kernels.mh import ops as mh_ops

        nbits = target.nbits
        b, c = state_shape

        @partial(
            jax.jit, static_argnames=("seg", "collect"), donate_argnums=(0,)
        )
        def advance(words, keys, step0s, *, seg, collect):
            s = words.shape[0]
            keys = _slot_chain_keys(keys)
            state0 = jnp.transpose(words, (1, 0, 2)).reshape(b, s * c)
            if backend.name == "fused":
                k0c, k1c = _fused_key_cols(keys, c)
                t0c = jnp.repeat(step0s.astype(jnp.int32), c)
                samples, acc = mh_ops.mh_sample_fused(
                    target.table, state0, k0c, k1c, n_steps=seg, t0=t0c,
                    nbits=nbits, p_bfr=backend.p_bfr, cc=c, block_c=block_c,
                )
            else:
                flips, u = jax.vmap(
                    lambda k, s0: backend.chunk(k, s0, seg, (b, c), nbits)
                )(keys, step0s)
                samples, acc = mh_ops.mh_sample(
                    target.table, state0, _chains_fold_mh(flips),
                    _chains_fold_mh(u), nbits=nbits, block_c=block_c,
                )
            # (seg, b, s*c) -> (s, seg, b, c); slot-major columns
            samples = jnp.moveaxis(samples.reshape(seg, b, s, c), 2, 0)
            acc = jnp.moveaxis(acc.reshape(b, s, c), 1, 0)
            words_out = samples[:, -1]
            logp = jax.vmap(
                lambda w: target.log_prob(w).astype(jnp.float32)
            )(words_out)
            if collect != "all":
                samples = samples[:, :0]
            return samples, words_out, logp, acc

    else:
        from repro.kernels.gibbs import ops as gibbs_ops

        logit_fn, consts = _fused_gibbs_logit(target)
        b, h, w = state_shape

        @partial(
            jax.jit, static_argnames=("seg", "collect"), donate_argnums=(0,)
        )
        def advance(words, keys, step0s, *, seg, collect):
            s = words.shape[0]
            keys = _slot_chain_keys(keys)
            state0 = words.reshape(s * b, h, w)
            if backend.name == "fused":
                k0b, k1b = _fused_key_cols(keys, b)
                t0b = jnp.repeat(step0s.astype(jnp.int32), b)
                samples, acc = gibbs_ops.gibbs_sweep_fused(
                    state0, k0b, k1b, logit_fn, n_steps=seg, t0=t0b,
                    lat_b=b, consts=consts,
                )
            else:
                u = jax.vmap(
                    lambda k, s0: backend.chunk(
                        k, s0, seg, (b, h, w), 1, need_flips=False
                    )[1]
                )(keys, step0s)
                u_fold = jnp.transpose(u, (1, 0, 2, 3, 4)).reshape(
                    seg, s * b, h, w
                )
                samples, acc = gibbs_ops.gibbs_sweep(
                    state0, u_fold, logit_fn,
                    parity0=jnp.repeat(step0s.astype(jnp.int32) % 2, b),
                    consts=consts,
                )
            # (seg, s*b, h, w) -> (s, seg, b, h, w); slot-major lattices
            samples = jnp.moveaxis(
                samples.reshape(seg, s, b, h, w), 1, 0
            )
            acc = acc.reshape(s, b, h, w)
            words_out = samples[:, -1]
            # the engine's Gibbs pseudo-likelihood of the final state
            logit = jax.vmap(target.conditional_logit)(words_out)
            logp = jnp.where(
                words_out == 1,
                jax.nn.log_sigmoid(logit),
                jax.nn.log_sigmoid(-logit),
            ).astype(jnp.float32)
            if collect != "all":
                samples = samples[:, :0]
            return samples, words_out, logp, acc

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
