"""The unified sampler engine — one chain datapath, four axes.

Every MCMC workload in this repo is a per-step state update driven by
the macro's randomness (paper Fig. 14): a random operand stream feeds an
update rule, and the chain state is rewritten in place.  ``MHEngine``
implements that loop exactly once and exposes four orthogonal, pluggable
axes (DESIGN.md §2):

  * **target**      — ``CallableTarget`` / ``TableTarget`` / ``TopKTarget``
                      (MH), or a conditional lattice model such as
                      ``workloads.ising.IsingModel`` (Gibbs)
  * **update rule** — ``mh`` (XOR-propose + accept test on the log-prob
                      ratio) vs ``gibbs`` (checkerboard conditional flip:
                      u < sigmoid(conditional logit), no reject)
  * **randomness**  — ``host`` (plain jax.random) vs ``cim`` (pseudo-read
                      bit-planes + MSXOR-debiased uniforms) vs ``fused``
                      (in-kernel counter RNG: pallas executors derive the
                      operands inside the kernel, scan draws the identical
                      stream through the shared cipher — DESIGN.md
                      §Randomness); all rules consume the same
                      accurate-[0,1] uniform stream, so backend
                      comparisons carry across rules
  * **execution**   — ``scan`` (pure-JAX ``lax.scan``) vs ``pallas`` (the
                      fused VMEM-resident kernel), with ``auto`` picking
                      by ``jax.default_backend()``

Each update rule has one executor per execution: a scan executor, and
one Pallas executor (``run_pallas_mh`` / ``run_pallas_gibbs``) that
launches the rule's kernels for solo runs, the chains axis and the
serving tier's packed advance alike.  The Pallas executor works on the
chain-folded layout (``fold_mh`` / ``fold_gibbs``, DESIGN.md
§Chains-axis) and takes the C per-chain keys and a scalar or per-chain
``step0``; a solo state is the C = 1 fold.  ``MHEngine.run`` is the one
dispatch over them.  For each update rule, the two executors consume
identical randomness operands and mirror each other op-for-op, so with
the same key they produce bit-identical sample streams (asserted in
tests/test_sampler_engine.py and tests/test_workloads.py).  Randomness
streams in chunks of ``chunk_steps`` — operands for step ``t`` depend
only on ``(key, step0 + t)`` — so chains of any length run in O(chunk)
operand memory, and a run resumed at ``step0 = s`` continues the exact
stream a longer run would have produced (the segment-invariance the
tempering subsystem builds on, DESIGN.md §Tempering).

A fifth axis, **collection** (DESIGN.md §Collection), decides how much
of the chain leaves the engine: ``collect="all"`` materialises every
post-step state (the historical behaviour and the default),
``"thin:<k>"`` keeps exactly the absolute steps ``(step0 + t) % k == 0``
(so thinned samples are a strided slice of the ``"all"`` stream,
invariant to chunking and segmentation), and ``"last"`` keeps nothing —
only (final_words, final_logp, accept_count) cross chunk boundaries, so
arbitrarily long chains run in O(state) output memory.  The collection
mode never changes the chain itself: operands are generated per absolute
step regardless of what is kept.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.samplers.randomness import (
    RandomnessBackend,
    chain_key,
    chain_keys,
    make_randomness_backend,
)
from repro.samplers.targets import logits_target

Array = jnp.ndarray

_EXECUTION_CHOICES = ("auto", "scan", "pallas")
_UPDATE_CHOICES = ("mh", "gibbs")


class IneligibleExecution(ValueError):
    """The engine refuses an execution for this target or state layout
    (pallas on an unfusable target, a chain state the kernels' grid
    cannot take).  The autotuner drops exactly these candidates; any
    other error, a compiler's refusal included, propagates."""


def parse_collect(collect: str) -> tuple[str, int]:
    """Validate a collection spec; returns ``(mode, k)``.

    ``"all"`` -> ("all", 1), ``"thin:<k>"`` -> ("thin", k) for k >= 1,
    ``"last"`` -> ("last", 0).  The kept-step set is defined on
    *absolute* step indices (DESIGN.md §Collection): ``thin:k`` keeps
    ``{t : (step0 + t) % k == 0}``, so thinning commutes with chunking
    and with segment resumption.
    """
    if collect == "all":
        return ("all", 1)
    if collect == "last":
        return ("last", 0)
    if isinstance(collect, str) and collect.startswith("thin:"):
        try:
            k = int(collect[len("thin:"):])
        except ValueError:
            k = 0
        if k >= 1:
            return ("thin", k)
    raise ValueError(
        f"collect must be 'all', 'last' or 'thin:<k>' (k >= 1), "
        f"got {collect!r}"
    )


def kept_count(n_steps: int, k: int, step0: int = 0) -> int:
    """Size of the ``thin:k`` kept set {t in [0, n_steps):
    (step0 + t) % k == 0}."""
    if k < 1:
        raise ValueError(f"thin stride k must be >= 1, got {k}")
    i0 = (-int(step0)) % k
    return 0 if i0 >= n_steps else (n_steps - i0 - 1) // k + 1


def _thin_offset(step0: int, k: int) -> int:
    """First kept relative step of a span starting at absolute ``step0``."""
    return (-int(step0)) % k


def _effective_chunk(n_steps: int, chunk: int, thin_k: int | None) -> int:
    """The one chunk-schedule rule shared by every executor: clamp to
    [1, n_steps], and under ``thin:k`` align to a multiple of k so every
    full chunk keeps exactly ``chunk // k`` rows (the per-chunk kept
    slice then has a static shape, which the scan executors' outer
    ``lax.scan`` requires)."""
    chunk = max(1, min(chunk, n_steps))
    if thin_k is not None and thin_k > 1:
        chunk = thin_k * max(1, chunk // thin_k)
    return chunk


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static configuration of the engine's update/randomness/execution axes."""

    p_bfr: float = 0.45              # proposal bit-flip rate (pseudo-read)
    randomness: str = "cim"          # host | cim | fused (§Randomness)
    rng_p_bfr: float | None = None   # [0,1]-RNG raw-bit bias (default p_bfr)
    rng_bit_width: int = 16          # u precision (cim backend)
    rng_stages: int = 3              # MSXOR stages (cim backend)
    update: str = "mh"               # mh | gibbs (DESIGN.md §2 update rule)
    execution: str = "auto"          # auto | scan | pallas
    chunk_steps: int = 64            # randomness streaming granularity
    block_c: int = 256               # pallas chain-axis block size
    num_chains: int = 1              # independent chains (DESIGN.md §Chains)
    collect: str = "all"             # all | thin:<k> | last (§Collection)

    def __post_init__(self):
        if self.execution not in _EXECUTION_CHOICES:
            raise ValueError(
                f"execution must be one of {_EXECUTION_CHOICES}, "
                f"got {self.execution!r}"
            )
        if self.update not in _UPDATE_CHOICES:
            raise ValueError(
                f"update must be one of {_UPDATE_CHOICES}, got {self.update!r}"
            )
        if self.randomness not in ("host", "cim", "fused"):
            raise ValueError(
                f"randomness must be host|cim|fused, got {self.randomness!r}"
            )
        if self.chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {self.chunk_steps}")
        if self.block_c < 1:
            raise ValueError(f"block_c must be >= 1, got {self.block_c}")
        if self.rng_bit_width < 1:
            raise ValueError(
                f"rng_bit_width must be >= 1, got {self.rng_bit_width}"
            )
        if self.rng_stages < 1:
            raise ValueError(f"rng_stages must be >= 1, got {self.rng_stages}")
        if self.num_chains < 1:
            raise ValueError(f"num_chains must be >= 1, got {self.num_chains}")
        parse_collect(self.collect)

    def backend(self) -> RandomnessBackend:
        return make_randomness_backend(
            self.randomness,
            p_bfr=self.p_bfr,
            rng_p_bfr=self.rng_p_bfr,
            rng_bit_width=self.rng_bit_width,
            rng_stages=self.rng_stages,
        )


class EngineResult(NamedTuple):
    samples: Array          # (K_kept, *chain_shape) uint32 post-step states
    #                         K_kept follows config.collect: n_steps under
    #                         "all", kept_count(...) under "thin:k", and 0
    #                         under "last" (final_words IS the sample)
    accept_count: Array     # (*chain_shape,) int32
    acceptance_rate: Array  # scalar float32
    final_words: Array      # (*chain_shape,) uint32
    final_logp: Array       # (*chain_shape,) float32
    n_steps: jnp.int32      # total steps run (not kept)


def resolve_execution(execution: str, target, update: str = "mh") -> str:
    """Backend dispatch rule (DESIGN.md §2): explicit override wins;
    ``auto`` = fused kernel on TPU for fusable targets, scan elsewhere.

    What makes a target fusable depends on the update rule: ``mh`` needs
    the distribution materialised as a table (held in VMEM); ``gibbs``
    needs a lattice model the checkerboard kernel knows how to sweep
    (``supports_fused_gibbs``)."""
    if update == "gibbs":
        if execution == "pallas":
            if not getattr(target, "supports_fused_gibbs", False):
                raise IneligibleExecution(
                    "pallas Gibbs execution needs a lattice model with a "
                    "fused checkerboard kernel (supports_fused_gibbs); "
                    "use execution='scan'"
                )
            return "pallas"
        # auto never fuses Gibbs: eligibility depends on the lattice shape
        # (periodic boundaries cannot pad to the 128-lane, DESIGN.md §3),
        # which dispatch cannot see.  Explicit pallas opts in.
        return "scan"
    if execution == "pallas":
        if target.table is None:
            raise IneligibleExecution(
                "pallas execution needs a table target (the fused kernel "
                "holds the distribution in VMEM); use a TableTarget or "
                "execution='scan'"
            )
        return "pallas"
    if execution == "scan":
        return "scan"
    if target.table is not None and jax.default_backend() == "tpu":
        return "pallas"
    return "scan"


def _host_side() -> bool:
    """True outside any jax trace — telemetry spans only make sense (and
    only read python ints safely) at the host level; traced re-entries
    (the serving tier's vmapped advance, tempering's jitted segments,
    ``run_engine`` and the compiled submit) skip instrumentation
    entirely."""
    return jax.core.trace_ctx.is_top_level()


def _host_spans(traced: bool = False):
    """``telemetry.span`` for a call that runs on the host with tracing
    on, else the no-op ``telemetry.null_span`` — decided once per call,
    so a loop pays no per-iteration check."""
    if telemetry.enabled() and not traced and _host_side():
        return telemetry.span
    return telemetry.null_span


def _mh_step(target, nbits: int, words, logp, acc, flip, u):
    """THE MH step — the only scan-side implementation in the repo.

    Mirrors the Pallas kernel body (kernels/mh/mh.py:_mh_kernel)
    op-for-op: XOR-propose, table/fn lookup, u < exp(min(dlogp, 0))
    accept, select (in-memory copy).
    """
    with jax.named_scope("mh.propose"):
        mask = jnp.uint32((1 << nbits) - 1)
        cand = jnp.bitwise_xor(words, flip & mask)
        logp_cand = target.log_prob(cand).astype(jnp.float32)
        delta = logp_cand - logp
        accept = jnp.logical_and(
            u < jnp.exp(jnp.minimum(delta, 0.0)), jnp.isfinite(logp_cand)
        )
    with jax.named_scope("mh.select"):
        words = jnp.where(accept, cand, words)        # in-memory copy
        logp = jnp.where(accept, logp_cand, logp)
        return words, logp, acc + accept.astype(jnp.int32)


def _run_scan_chunked(
    make_xs, step_fn, carry, n_steps, chunk, step0, collect, unroll=1
):
    """THE scan-side chunk scheduler — the full/remainder scaffolding both
    scan executors share (mh and gibbs differ only in their operand maker
    and step body).

    ``make_xs(start, n)`` materialises the operand pytree for absolute
    steps [start, start + n); ``step_fn(carry, x) -> carry`` advances one
    step, with ``carry[0]`` the chain state that feeds the sample stream.
    ``collect`` is a parsed ``(mode, k)`` (see ``parse_collect``): "all"
    emits every post-step state, "thin" emits the per-chunk strided kept
    slice (chunks are k-aligned by ``_effective_chunk``, so every full
    chunk keeps the same row count and the outer scan stays shape-static),
    and "last" emits nothing — the inner scan carries only the state, so
    output memory is O(state) for any chain length.  ``unroll`` unrolls
    the inner scan where it stays a loop, at least two unrolled
    iterations: shorter, it is straight-line code, in which XLA's CPU
    backend fuses the chained steps' cipher draws into kernels it takes
    minutes to compile (the stream and the result do not depend on it).
    """
    mode, k = collect
    chunk = _effective_chunk(n_steps, chunk, k if mode == "thin" else None)
    i0 = _thin_offset(step0, k) if mode == "thin" else 0
    n_full, rem = divmod(n_steps, chunk)

    def span(c, start, n):
        def body(c, x):
            c = step_fn(c, x)
            return c, (None if mode == "last" else c[0])

        c, ys = jax.lax.scan(
            body, c, make_xs(start, n), unroll=unroll if n >= 2 * unroll else 1
        )
        if mode == "thin":
            # start ≡ step0 (mod k) for every span, so the kept offset is
            # the same static i0 and the slice shape is chunk-invariant
            ys = ys[i0::k]
        return c, ys

    pieces = []
    if n_full:
        starts = step0 + jnp.arange(n_full, dtype=jnp.int32) * chunk
        carry, stacked = jax.lax.scan(
            lambda c, s: span(c, s, chunk), carry, starts
        )
        if mode != "last":
            pieces.append(stacked.reshape(-1, *stacked.shape[2:]))
    if rem:
        carry, tail = span(carry, step0 + n_full * chunk, rem)
        if mode != "last":
            pieces.append(tail)
    if mode == "last":
        samples = jnp.zeros((0, *carry[0].shape), jnp.uint32)
    else:
        samples = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)
    return samples, carry


def _run_scan(
    key, target, backend, nbits, n_steps, chunk, step0, init_words, collect,
    init_logp=None,
):
    shape = init_words.shape
    words0 = init_words.astype(jnp.uint32)
    logp0 = (
        target.log_prob(words0) if init_logp is None else init_logp
    )
    carry = (
        words0,
        logp0.astype(jnp.float32),
        jnp.zeros(shape, jnp.int32),
    )

    def make_xs(start, n):
        with jax.named_scope("mh.draw"):
            return backend.chunk(key, start, n, shape, nbits)

    def step_fn(c, x):
        flip, u = x
        return _mh_step(target, nbits, *c, flip, u)

    samples, (words, logp, acc) = _run_scan_chunked(
        make_xs, step_fn, carry, n_steps, chunk, step0, collect
    )
    return samples, acc, words, logp


def _step0_base(step0, extent: int):
    """``(step0, base)`` for the pallas executors.  The chunk *schedule*
    (the python loop) is always static, but the absolute-step base is a
    runtime operand of the fused kernels (and of the checkerboard
    parity), so a traced ``step0`` is fine for collect="all"/"last" —
    successive serving segments and packed slots reuse one compiled
    program.  ``step0`` comes back a python int where it is concrete (the
    eager loop then adds chunk starts in python); ``base`` is what the
    kernels read: ``step0`` itself, or — for a ``(C,)`` step0, one base
    per chain — each chain's base repeated over its ``extent`` columns
    or lattices, chain-major like the fold.  Only thinning needs a
    concrete int (the kept-slice stride is resolved at python level),
    which ``_parse_collect`` enforces with an actionable error upstream."""
    if jnp.ndim(step0):
        return step0, jnp.repeat(jnp.asarray(step0, jnp.int32), extent)
    try:
        step0 = int(step0)
    except TypeError:
        pass
    return step0, step0


@functools.lru_cache(maxsize=None)
def _chunk_writer(ndim: int):
    """Donating jitted chunk-buffer update for the eager pallas driver:
    ``out[pos : pos + rows.shape[0]] = rows`` as one compiled program
    whose output aliases the donated input, so each chunk write touches
    only the written rows.  The historical eager assembly appended to a
    ``pieces`` list and paid a full-stream ``concatenate`` copy at the
    end (plus O(chunks) buffer lifetimes); a bare eager
    ``dynamic_update_slice`` would be worse still — a whole-buffer copy
    per chunk, O(K²/chunk) traffic.  ``pos`` is a traced operand, so one
    compile serves every chunk boundary."""

    def write(out, rows, pos):
        return jax.lax.dynamic_update_slice(out, rows, (pos,) + (0,) * ndim)

    return jax.jit(write, donate_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5, 6, 7), inline=True)
def _draw_folded(backend, fold, keys, t0, n_steps, shape, nbits, need_flips):
    """The operand kernels' chunk draw (host/cim randomness): each of the
    C chains' operands for steps [t0, t0 + n_steps) at its own key — and
    its own base, where ``t0`` is ``(C,)`` — folded by the rule's
    ``fold``.  One compiled program per static signature with the keys
    and ``t0`` traced, so an eager chunk is one dispatch for any C; under
    a trace ``inline=True`` splices the body into the caller's program.
    At C = 1 the fold is the identity, and the one chain draws without a
    vmap."""

    def draw(k, t):
        return backend.chunk(k, t, n_steps, shape, nbits, need_flips)

    if keys.shape[0] == 1:
        return draw(keys[0], jnp.reshape(t0, ()))
    per_chain = jax.vmap(draw, in_axes=(0, 0 if jnp.ndim(t0) else None))(
        keys, t0
    )
    return jax.tree.map(fold, per_chain)


def _drive_pallas_chunks(
    run_chunk, init_state, n_steps, chunk, step0, collect, draw=None,
    randomness=None,
):
    """THE fused-executor chunk scheduler — the python chunk loop both
    pallas executors share.

    ``run_chunk(state, start, n, *operands)`` launches one kernel program
    for relative steps [start, start + n) and returns (samples (n, *state
    shape) uint32, per-site count (*state shape) int32).  ``draw(start,
    n)`` returns the chunk's randomness operands for the operand kernels
    through ``_draw_folded`` (``randomness`` names the backend); fused
    randomness draws in the kernel and passes no ``draw``.  Kept rows are
    written straight into one preallocated output buffer via
    ``lax.dynamic_update_slice``: under a trace (``run_engine`` or any
    caller-side jit — which also collapses the loop into a single
    dispatch) XLA aliases the update in place, and eagerly the write
    goes through the donating jitted ``_chunk_writer`` so the buffer is
    reused in place as well — no per-chunk ``pieces`` list, no final
    full-stream ``concatenate`` copy, O(rows-written) traffic per chunk
    either way.  Under "last" samples are dropped at the chunk boundary
    and only (state, count) survive.  The chunk *schedule* (the python
    loop) is static; ``step0`` may be traced (``_step0_base``) except
    under thinning, whose kept-slice arithmetic is python-level
    (enforced upstream by ``_parse_collect``).

    Telemetry (DESIGN.md §Telemetry): a host-side loop with tracing on
    records one ``engine.chunk`` span per chunk, holding a
    ``randomness.draw`` span around the operand draw and an
    ``engine.emit`` span around the kept-row write and the state/count
    glue; the caller's ``engine.finish`` covers the job's tail.  Each
    ``randomness.draw`` span carries ``jit_cache="miss"`` if the draw
    compiled, else ``"hit"``, for solo and chains runs alike.  Under a
    trace the loop is staged, not run, and records nothing.
    """
    mode, k = collect
    chunk = _effective_chunk(n_steps, chunk, k if mode == "thin" else None)
    state = init_state
    acc = jnp.zeros(state.shape, jnp.int32)
    if mode == "all":
        n_keep = n_steps
    elif mode == "thin":
        n_keep = kept_count(n_steps, k, step0)
    else:
        n_keep = 0
    traced = isinstance(state, jax.core.Tracer)
    span = _host_spans(traced)
    draw_cache = _draw_folded._cache_size if span is telemetry.span else None
    out = jnp.zeros((n_keep, *state.shape), jnp.uint32)
    zeros = (0,) * state.ndim
    pos = 0

    def emit(rows):
        nonlocal out, pos
        if traced:
            out = jax.lax.dynamic_update_slice(out, rows, (pos, *zeros))
        else:
            out = _chunk_writer(state.ndim)(out, rows, pos)
        pos += rows.shape[0]

    for start in range(0, n_steps, chunk):
        n = min(chunk, n_steps - start)
        with span("engine.chunk", start=start, n=n):
            operands = ()
            if draw is not None:
                with span("randomness.draw", backend=randomness, n=n) as sp:
                    before = draw_cache() if draw_cache else 0
                    operands = draw(start, n)
                    if draw_cache:
                        sp.set(
                            jit_cache="miss" if draw_cache() > before
                            else "hit"
                        )
            samples, a = run_chunk(state, start, n, *operands)
            with span("engine.emit"):
                state = samples[-1]
                acc = acc + a
                if mode == "all":
                    emit(samples)
                elif mode == "thin":
                    i0 = _thin_offset(step0 + start, k)
                    if i0 < n:
                        emit(samples[i0::k])
    return out, acc, state


@functools.partial(jax.jit, static_argnums=(1,), inline=True)
def _fused_key_cols(keys, repeat: int):
    """Per-column/lattice chain-key words for the fused kernels: the two
    uint32 words of each of the C stacked chain keys (kernels/rng), each
    repeated over its chain's ``repeat`` columns or lattices —
    chain-major, the fold's layout.  This is the ONLY randomness state
    the fused kernels receive (8 bytes per column/lattice per chunk,
    replacing per-step operand planes); one dispatch a job eagerly."""
    from repro.kernels import rng  # avoid import cycle

    kw = jax.vmap(lambda k: jnp.stack(rng.key_words(k)))(keys)
    return jnp.repeat(kw[:, 0], repeat), jnp.repeat(kw[:, 1], repeat)


# --- the Pallas executors and the chains fold (DESIGN.md §Chains-axis) ------
#
# One executor per update rule launches that rule's kernels, for solo
# runs, the chains axis and the serving tier's packed advance alike.  It
# works on the *folded* layout: C independent chains ride the kernel's
# batched grid — the compartment axis (mh, state (B, C·Cc), grid
# (B, C·Cc/BLOCK_C)) or the lattice-batch axis (gibbs, state (C·B, H, W),
# grid (C·B,)) — chain-major, so chain c owns one contiguous block.  Both
# grids block over exactly the folded axis and every op is per-column /
# per-lattice, so folding preserves bit-parity; a solo state is its own
# C = 1 fold.  Per-chain randomness is counter-derived — chain c streams
# from fold_in(key, c), then per-step fold_in(·, t) — so chain c of a
# C-chain run is bit-identical to a solo run with chain_id=c.


def fold_mh(x):
    """(C, ..., B, Cc) -> (..., B, C·Cc): chains ride the MH compartment
    axis, chain c owning columns [c·Cc, (c+1)·Cc).  States, kept samples
    and operand chunks fold alike."""
    x = jnp.moveaxis(x, 0, -2)
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def unfold_mh(x, num_chains: int):
    """(..., B, C·Cc) -> (C, ..., B, Cc): the inverse of ``fold_mh``."""
    x = x.reshape(*x.shape[:-1], num_chains, x.shape[-1] // num_chains)
    return jnp.moveaxis(x, -2, 0)


def fold_gibbs(x):
    """(C, ..., B, H, W) -> (..., C·B, H, W): chains ride the Gibbs
    lattice-batch axis, chain c owning lattices [c·B, (c+1)·B)."""
    x = jnp.moveaxis(x, 0, -4)
    return x.reshape(
        *x.shape[:-4], x.shape[-4] * x.shape[-3], *x.shape[-2:]
    )


def unfold_gibbs(x, num_chains: int):
    """(..., C·B, H, W) -> (C, ..., B, H, W): the inverse of
    ``fold_gibbs``."""
    x = x.reshape(
        *x.shape[:-3], num_chains, x.shape[-3] // num_chains, *x.shape[-2:]
    )
    return jnp.moveaxis(x, -4, 0)


def _fused_gibbs_logit(target):
    """(logit_fn, consts) for the fused kernel: models whose conditional
    closes over array parameters expose them as ``fused_consts`` plus a
    ``fused_logit(state, *consts)`` sharing the scan-side math body —
    kernel traces cannot capture array closures (DESIGN.md §Tempering)."""
    consts = tuple(getattr(target, "fused_consts", ()) or ())
    if consts:
        return target.fused_logit, consts
    return target.conditional_logit, ()


def run_pallas_mh(
    engine, keys, target, state, n_steps: int, step0, collect, chunk=None
):
    """THE Pallas MH executor: solo runs, the chains axis and the serving
    tier's packed advance launch the MH kernels only through here.

    ``state`` is C chains' folded (B, C·Cc) state (``fold_mh``; a solo
    (B, Cc) state is its own fold), ``keys`` the C stacked per-chain
    keys, already derived (``chain_key``), and ``step0`` a scalar or a
    ``(C,)`` vector of per-chain absolute-step bases — the vector only
    under collect "all" or "last", whose kept set does not depend on it.
    ``collect`` is a parsed ``(mode, k)`` (``parse_collect``) and
    ``chunk`` the steps one kernel call runs (default: the engine's
    ``chunk_steps``).  Returns folded ``(samples, accept_count,
    final_words)``; ``final_logp`` gives the final log-prob."""
    from repro.kernels.mh import ops as mh_ops  # avoid import cycle

    backend, block_c, nbits = (
        engine.randomness, engine.config.block_c, target.nbits
    )
    b, cc = state.shape[0], state.shape[1] // keys.shape[0]
    step0, base = _step0_base(step0, cc)

    draw = None
    if backend.name == "fused":
        k0c, k1c = _fused_key_cols(keys, cc)

        def run_chunk(state, start, n):
            return mh_ops.mh_sample_fused(
                target.table, state, k0c, k1c, n_steps=n, t0=base + start,
                nbits=nbits, p_bfr=backend.p_bfr, cc=cc, block_c=block_c,
            )
    else:

        def draw(start, n):
            return _draw_folded(
                backend, fold_mh, keys, step0 + start, n, (b, cc), nbits,
                True,
            )

        def run_chunk(state, start, n, flips, u):
            return mh_ops.mh_sample(
                target.table, state, flips, u, nbits=nbits, block_c=block_c
            )

    return _drive_pallas_chunks(
        run_chunk, state.astype(jnp.uint32), n_steps,
        chunk or engine.config.chunk_steps, step0, collect, draw,
        backend.name,
    )


def run_pallas_gibbs(
    engine, keys, target, state, n_steps: int, step0, collect, chunk=None
):
    """THE Pallas Gibbs executor: as ``run_pallas_mh``, over C chains'
    folded (C·B, H, W) lattice state (``fold_gibbs``; a solo (B, H, W)
    state is its own fold).  The absolute-step base also carries each
    lattice's checkerboard parity.  Returns folded ``(samples,
    flip_count, final_words)``."""
    from repro.kernels.gibbs import ops as gibbs_ops  # avoid import cycle

    backend = engine.randomness
    lat_b = state.shape[0] // keys.shape[0]
    step0, base = _step0_base(step0, lat_b)
    logit_fn, consts = _fused_gibbs_logit(target)

    draw = None
    if backend.name == "fused":
        k0b, k1b = _fused_key_cols(keys, lat_b)

        def run_chunk(state, start, n):
            return gibbs_ops.gibbs_sweep_fused(
                state, k0b, k1b, logit_fn, n_steps=n, t0=base + start,
                lat_b=lat_b, consts=consts,
            )
    else:

        def draw(start, n):
            return _draw_folded(
                backend, fold_gibbs, keys, step0 + start, n,
                (lat_b, *state.shape[1:]), 1, False,
            )

        def run_chunk(state, start, n, _flips, u):
            return gibbs_ops.gibbs_sweep(
                state, u, logit_fn, parity0=(base + start) % 2,
                consts=consts,
            )

    return _drive_pallas_chunks(
        run_chunk, state.astype(jnp.uint32), n_steps,
        chunk or engine.config.chunk_steps, step0, collect, draw,
        backend.name,
    )


# each rule's Pallas executor and its fold: what ``MHEngine.run`` and the
# serving tier's packed advance look up
PALLAS_EXECUTORS = {
    "mh": (run_pallas_mh, fold_mh, unfold_mh),
    "gibbs": (run_pallas_gibbs, fold_gibbs, unfold_gibbs),
}


def _count_step_draws(engine, target, n_steps: int) -> None:
    """Add one engine call's scan Gibbs half-sweeps, each of which draws
    its uniforms in the step, to the ``gibbs_step_draw_total`` counter —
    on the host only, once per call (DESIGN.md §Telemetry); a traced
    re-entry counts nothing."""
    cfg = engine.config
    if cfg.update == "gibbs" and _host_side() and resolve_execution(
        cfg.execution, target, "gibbs"
    ) == "scan":
        telemetry.counter(
            "gibbs_step_draw_total",
            "Gibbs half-sweeps whose uniforms the scan step drew itself",
        ).inc(n_steps)


def _gibbs_step(target, state, acc, u, parity):
    """THE Gibbs half-sweep — the only scan-side implementation in the repo.

    Mirrors the Pallas kernel body (kernels/gibbs/gibbs.py:_gibbs_kernel)
    op-for-op: conditional logit from the current neighbours, draw the
    site's new value as u < sigmoid(logit), write it on the active
    checkerboard colour only.  There is no reject — ``acc`` counts sites
    whose value actually changed (the flip count)."""
    with jax.named_scope("gibbs.conditional"):
        logit = target.conditional_logit(state)
        new = (u < jax.nn.sigmoid(logit)).astype(jnp.uint32)
    with jax.named_scope("gibbs.select"):
        active = target.update_mask(state.shape, parity)
        nxt = jnp.where(active, new, state)
        return nxt, acc + (nxt != state).astype(jnp.int32)


def _run_scan_gibbs(
    key, target, backend, n_steps, chunk, step0, init_words, collect
):
    """Scan Gibbs.  Each half-sweep draws its own uniforms in the step
    body (``backend.step``, the chunk draw's stream bit for bit), so no
    (chunk, B, H, W) operand exists and XLA fuses the cipher, the
    conditional, the select and the flip count into one pass over the
    lattice (DESIGN.md §2)."""
    shape = init_words.shape
    carry = (init_words.astype(jnp.uint32), jnp.zeros(shape, jnp.int32))

    def make_xs(start, n):
        return start + jnp.arange(n, dtype=jnp.int32)

    def step_fn(c, t):
        # gibbs draws no proposal — the operand-lean u-only path
        with jax.named_scope("gibbs.draw"):
            _, u = backend.step(key, t, shape, 1, need_flips=False)
        return _gibbs_step(target, *c, u, t % 2)

    samples, (state, acc) = _run_scan_chunked(
        make_xs, step_fn, carry, n_steps, chunk, step0, collect, unroll=2
    )
    return samples, acc, state


def _gibbs_logp(target, words):
    """Per-site conditional log-prob (pseudo-likelihood) of a Gibbs
    state: its ``final_logp``."""
    logit = target.conditional_logit(words)
    return jnp.where(
        words == 1, jax.nn.log_sigmoid(logit), jax.nn.log_sigmoid(-logit)
    ).astype(jnp.float32)


def final_logp(update: str, target, words):
    """The ``final_logp`` a run reports for its final ``words``: the
    target's log-prob under ``mh`` (table targets read (B, C) words, so
    leading chain or slot axes are mapped over) and the per-site
    pseudo-likelihood under ``gibbs`` — the one place each is derived
    (the scan MH executor carries its own)."""
    if update == "gibbs":
        return _gibbs_logp(target, words)
    log_prob = target.log_prob
    for _ in range(words.ndim - 2):
        log_prob = jax.vmap(log_prob)
    return log_prob(words).astype(jnp.float32)


def _result(samples, acc, words, logp, n_steps: int, size: int):
    """The ``EngineResult`` of a run over ``size`` sites."""
    total = jnp.float32(n_steps) * jnp.float32(max(1, size))
    return EngineResult(
        samples=samples,
        accept_count=acc,
        acceptance_rate=jnp.sum(acc).astype(jnp.float32) / total,
        final_words=words,
        final_logp=logp,
        n_steps=jnp.int32(n_steps),
    )


class MHEngine:
    """One sampler engine, pluggable on all four axes (the name predates
    the ``gibbs`` update rule; ``SamplerEngine`` aliases it).

    Methods are traceable (no internal ``jax.jit``) so thin wrappers can
    jit at whatever boundary fits their API; ``run_engine`` below is the
    ready-made jitted entry.
    """

    def __init__(self, config: EngineConfig = EngineConfig()):
        self.config = config
        self._backend = config.backend()

    @property
    def randomness(self) -> RandomnessBackend:
        return self._backend

    def submit(self, plan, *, compiled: bool = False):
        """Run a validated ``RunPlan``; returns a re-submittable
        ``RunHandle`` (DESIGN.md §Run-API) — the documented public entry.

        ``compiled=True`` routes through the cached jitted dispatcher
        (one device dispatch per distinct static signature; needs a
        concrete ``step0``).  The default direct path is traceable, so
        plans built inside jitted/vmapped programs (tempering segments,
        the serving tier's packed advance) submit the same way.
        """
        from repro.samplers.plan import submit  # lazy: plan imports engine

        return submit(self, plan, compiled=compiled)

    def run(
        self, key, target, n_steps: int, init_words, *,
        chain_id: int = 0, mesh=None, step0=0, collect: str | None = None,
        init_logp=None,
    ) -> EngineResult:
        """Run ``n_steps`` of the configured update rule from
        ``init_words``; keep what ``collect`` says (default: every state).

        ``collect`` overrides ``config.collect`` for this run (DESIGN.md
        §Collection): ``"all"`` materialises every post-step state,
        ``"thin:<k>"`` keeps the absolute steps ``(step0 + t) % k == 0``
        (bit-identical to the strided slice ``all[(-step0) % k :: k]``,
        so thinning commutes with chunking *and* with ``step0``
        segmentation), ``"last"`` keeps none — ``final_words`` /
        ``final_logp`` / ``accept_count`` are the whole result and
        ``samples`` is a (0, *chain_shape) placeholder.  The chain
        dynamics are identical in all three modes.  ``"thin:<k>"``
        requires a concrete ``step0`` (the kept count is shape-static).

        ``init_logp`` (solo MH scan only) seeds the carried log-prob
        instead of re-evaluating ``target.log_prob(init_words)`` — pass
        the previous segment's ``final_logp`` when resuming so segmented
        runs touch the target exactly once per step, like an unsegmented
        run (the serving tier's donated-carry contract, DESIGN.md
        §Serving).  It must equal ``target.log_prob(init_words)``;
        nothing is re-checked.

        ``step0`` offsets the randomness stream (and the Gibbs
        checkerboard parity) by an absolute step count: operands for
        step ``t`` of this run are those of absolute step ``step0 + t``,
        so a run resumed from ``(final_words, step0=s)`` continues the
        exact stream of one unsegmented run — the segment-invariance the
        tempering subsystem's swap boundaries rely on (DESIGN.md
        §Tempering).  Both executors accept a traced ``step0`` for
        ``collect="all"``/``"last"`` — the fused pallas kernels take the
        absolute-step base (and the Gibbs checkerboard parity it
        carries) as a runtime operand, so segments at different offsets
        reuse one compiled program; only ``"thin:<k>"`` needs a concrete
        int (the kept count is shape-static).

        ``mh``: ``init_words`` is (B, C) for table targets (B independent
        targets x C lock-step chains), any shape for callable targets.
        ``gibbs``: ``init_words`` is the lattice state (..., H, W) of
        {0, 1} spin words (strictly (B, H, W) under pallas execution);
        each step is one checkerboard half-sweep, ``accept_count`` is the
        per-site flip count, and ``final_logp`` is the per-site
        conditional log-prob (pseudo-likelihood) of the final state.

        **Chains axis** (DESIGN.md §Chains-axis): with
        ``config.num_chains == C > 1`` this runs C independent chains in
        one device program; ``init_words`` must carry a leading (C,)
        axis (broadcast a shared solo init yourself — the engine never
        guesses, a coincidental first dim would be misread) and every
        result field gains that leading axis.  Randomness is counter-derived per
        ``(chain_id, absolute_step)``, so chain c of a C-chain run is
        bit-identical to a solo run with ``chain_id=c``; in a multi-chain
        run ``chain_id`` acts as the chain-id *base* (chains cover
        [chain_id, chain_id + C), so two C-chain runs with bases 0 and C
        compose into the 2C-chain run).  ``mesh`` (a
        concrete ``jax.sharding.Mesh``) shards the chain axis across
        devices via ``shard_map`` under the "chains" sharding rule;
        chains never communicate, so sharded == unsharded bit-for-bit.

        On the host, a scan Gibbs call adds its half-sweeps to the
        ``gibbs_step_draw_total`` counter (DESIGN.md §Telemetry).
        """
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        if isinstance(step0, int) and step0 < 0:
            raise ValueError(f"step0 must be >= 0, got {step0}")
        collect = self._parse_collect(collect, step0)
        cfg, backend = self.config, self._backend
        n_chains, mh = cfg.num_chains, cfg.update == "mh"
        if not mh and not hasattr(target, "conditional_logit"):
            raise ValueError(
                "gibbs update needs a conditional target exposing "
                "conditional_logit/update_mask (e.g. workloads.ising."
                f"IsingModel); got {type(target).__name__}"
            )
        execution = resolve_execution(cfg.execution, target, cfg.update)
        if init_logp is not None and (
            n_chains > 1 or not mh or execution != "scan"
        ):
            raise ValueError(
                "init_logp resumes the solo MH scan carry only — the Gibbs "
                "carry holds no log-prob, the chains axis derives its own "
                "per-chain carries, and the pallas MH kernel re-derives the "
                "log-prob from the state words"
            )
        _count_step_draws(self, target, n_steps)
        init = jnp.asarray(init_words)
        # the leading axis is ALWAYS the chain axis — never guessed from
        # shape coincidences (a solo init whose first dim happens to equal
        # num_chains would be silently misread); broadcast explicitly
        if n_chains > 1 and (init.ndim == 0 or init.shape[0] != n_chains):
            raise ValueError(
                f"multi-chain init_words must carry a leading "
                f"(num_chains={n_chains},) axis, got {init.shape}; "
                f"broadcast a solo init with "
                f"jnp.broadcast_to(init, ({n_chains}, *init.shape))"
            )
        # body(key(s), state) -> (samples, acc, words, logp or None)
        if execution == "scan":

            def body(k, w):  # one chain; C > 1 vmaps it
                if mh:
                    return _run_scan(
                        k, target, backend, target.nbits, n_steps,
                        cfg.chunk_steps, step0, w, collect, init_logp,
                    )
                return (*_run_scan_gibbs(
                    k, target, backend, n_steps, cfg.chunk_steps, step0, w,
                    collect,
                ), None)
        else:
            lead = "(num_chains, " if n_chains > 1 else "("
            layout = lead + ("B, C) chain" if mh else "B, H, W) lattice")
            if init.ndim != (2 if mh else 3) + (n_chains > 1):
                raise IneligibleExecution(
                    f"pallas execution expects {layout} state, got "
                    f"{init.shape}"
                )
            run_pallas, fold, unfold = PALLAS_EXECUTORS[cfg.update]

            def body(ks, w):  # C chains at once, through the fold
                if n_chains == 1:  # a solo state is its own fold
                    out = run_pallas(self, ks, target, w, n_steps, step0,
                                     collect)
                else:
                    out = tuple(unfold(x, ks.shape[0]) for x in run_pallas(
                        self, ks, target, fold(w), n_steps, step0, collect
                    ))
                return (*out, None)

        if n_chains == 1:
            k = chain_key(key, chain_id)
            samples, acc, words, logp = body(
                k if execution == "scan" else k[None], init
            )
        else:
            if execution == "scan":
                body = jax.vmap(body)
            from repro.distributed.sharding import shard_over_chains

            sharded = shard_over_chains(body, mesh, n_chains)
            if sharded is not None:
                body = jax.jit(sharded)
            samples, acc, words, logp = body(
                chain_keys(key, n_chains, base=chain_id), init
            )
        # the job's tail: the final log-prob is re-derived from the final
        # state wherever the executor carries none
        with _host_spans()("engine.finish"):
            if logp is None:
                logp = final_logp(cfg.update, target, words)
            return _result(samples, acc, words, logp, n_steps, init.size)

    def _parse_collect(self, collect: str | None, step0) -> tuple[str, int]:
        """Resolve the run-level override against the config default and
        pin down thin's static-shape requirement."""
        mode_k = parse_collect(
            self.config.collect if collect is None else collect
        )
        if mode_k[0] == "thin":
            try:
                int(step0)
            except TypeError as e:
                raise ValueError(
                    "collect='thin:<k>' needs a concrete (python int) step0 "
                    "— the kept-sample count is part of the output shape, "
                    "so a traced stream offset cannot size it.  Either pass "
                    "step0 as a python int (re-jitting per offset), or keep "
                    "the traced offset with collect='all' and take the "
                    "host-side strided slice samples[(-step0) % k :: k] "
                    "afterwards — bit-identical to engine thin on absolute "
                    "steps, and exactly the serving tier's fallback "
                    "(serving/executor.py, DESIGN.md §Serving).  "
                    "collect='last' also accepts traced offsets."
                ) from e
        return mode_k

    def sample_tokens(
        self,
        key,
        logits,
        n_steps: int,
        temperature: float = 1.0,
        top_k: int = 0,
        init_tokens=None,
    ) -> tuple[Array, EngineResult]:
        """Draw one token per row of ``logits`` (B, V): one chain per row.

        Returns (tokens (B,) int32, full EngineResult).  ``init_tokens``
        seeds the chains (the macro's x^(0) written into the bitcells);
        defaults to the row argmax — a guaranteed finite-logp start.

        A decode step brings new logits every call, so it runs as one
        compiled program keyed on the engine's configuration and the
        shapes, with the logits a traced argument: a new table never
        recompiles (DESIGN.md §Run-API); inside a caller's trace that
        program is staged into the caller's.  On the host the call
        records a ``tokens.sample`` span and adds its rows and
        chain-steps to the ``tokens_rows_total`` and
        ``tokens_steps_total`` counters (DESIGN.md §Telemetry).
        """
        rows = int(jnp.shape(logits)[0])
        with _host_spans()("tokens.sample", rows=rows, n_steps=n_steps):
            out = _sample_tokens_compiled(
                key, logits, init_tokens, config=self.config,
                n_steps=n_steps, temperature=temperature, top_k=top_k,
            )
        if _host_side():
            telemetry.counter(
                "tokens_rows_total", "rows sampled by sample_tokens"
            ).inc(rows)
            telemetry.counter(
                "tokens_steps_total", "MH chain-steps run by sample_tokens"
            ).inc(rows * n_steps)
        return out

    def _sample_tokens(
        self, key, logits, n_steps: int, temperature: float, top_k: int,
        init_tokens,
    ) -> tuple[Array, EngineResult]:
        with jax.named_scope("tokens.table"):
            target = logits_target(
                logits, temperature=temperature, top_k=top_k
            )
            if init_tokens is None:
                init = jnp.argmax(target.table, axis=-1).astype(jnp.uint32)
            else:
                init = jnp.clip(
                    init_tokens.astype(jnp.uint32), 0,
                    target.table.shape[-1] - 1,
                )
        result = self.run(key, target, n_steps, init[:, None])
        tokens = target.decode(result.final_words)[:, 0].astype(jnp.int32)
        return tokens, result


@functools.partial(
    jax.jit, static_argnames=("config", "n_steps", "temperature", "top_k")
)
def _sample_tokens_compiled(
    key, logits, init_tokens, *, config, n_steps, temperature, top_k
):
    """The decode step's one program: ``sample_tokens`` staged for an
    engine of ``config`` (a frozen dataclass, hashed by value, so every
    engine of one configuration shares the program)."""
    return MHEngine(config)._sample_tokens(
        key, logits, n_steps, temperature, top_k, init_tokens
    )


SamplerEngine = MHEngine  # the engine outgrew its MH-only name in PR 2


def run_engine(
    key, init_words, *, engine: MHEngine, target, n_steps: int,
    chain_id: int = 0, step0: int = 0, collect: str | None = None,
):
    """Deprecated jitted entry — build a ``RunPlan`` and call
    ``engine.submit(plan, compiled=True)`` instead (DESIGN.md §Run-API).

    Bit- and dispatch-compatible with the historical signature: routes
    through the same cached jitted dispatcher (``engine``/``target`` are
    identity-hashed statics — reuse the same instances to reuse the
    trace), and the warning fires per call because it lives outside the
    trace.
    """
    import warnings

    from repro.samplers.plan import RunPlan, submit

    warnings.warn(
        "run_engine is deprecated; build a samplers.RunPlan and call "
        "engine.submit(plan, compiled=True) (DESIGN.md §Run-API)",
        DeprecationWarning,
        stacklevel=2,
    )
    plan = RunPlan(
        target=target, n_steps=n_steps, init_words=init_words, key=key,
        chain_id=chain_id, step0=step0, collect=collect,
    )
    return submit(engine, plan, compiled=True).result
