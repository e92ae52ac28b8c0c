"""2-D Ising / Markov-random-field workload — checkerboard Gibbs sampling.

The MRF inference workload of Bashizade et al. (PAPERS.md) phrased for
the CIM macro: each lattice site is one 1-bit compartment word, the
4-neighbour coupling is the MRF edge potential, and one engine step is
one checkerboard half-sweep (all sites of one colour update in parallel
— their neighbourhoods are frozen, so the parallel update is exact
Gibbs).  The conditional flip consumes the macro's accurate-[0,1]
uniform: p(s_i = +1 | neighbours) = sigmoid(2 (beta * sum_j s_j + h)).

``IsingModel`` is the engine's first *conditional* target: instead of a
``log_prob`` over words it exposes ``conditional_logit`` +
``update_mask``, the contract of the ``gibbs`` update rule (DESIGN.md
§2/§Workloads).  ``conditional_logit`` is the one implementation of the
conditional — the scan executor steps it directly and the fused kernel
(kernels/gibbs/gibbs.py) traces the very same bound method — which is
what makes scan/pallas parity an array-equality test.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import samplers

Array = jnp.ndarray


def spin(words: Array) -> Array:
    """{0, 1} words as float32 spins -1/+1, via int32: Mosaic has no
    uint32 -> float32 cast."""
    return 2.0 * words.astype(jnp.int32).astype(jnp.float32) - 1.0


def periodic_roll(x: Array, shift: int, axis: int) -> Array:
    """``jnp.roll(x, shift, axis)``, value for value, built from
    positive pads and static slices.

    A lattice model's conditional rolls the carried state, and this form
    lets XLA fuse every roll into the half-sweep's one loop over the
    lattice: ``jnp.roll``'s concatenate of slices materialises the
    slices in HBM when the roll runs along the lane axis.  Mosaic lowers
    the same ops inside the Gibbs kernels (DESIGN.md §2)."""
    axis %= x.ndim
    n = x.shape[axis]
    k = shift % n
    if k == 0:
        return x

    def moved(lo: int, hi: int, start: int) -> Array:
        pads = [(0, 0, 0)] * x.ndim
        pads[axis] = (lo, hi, 0)
        padded = jax.lax.pad(x, jnp.zeros((), x.dtype), pads)
        return jax.lax.slice_in_dim(padded, start, start + n, axis=axis)

    # i >= k reads x[i - k]; i < k wraps round to x[n - k + i]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < k, moved(0, n - k, n - k), moved(k, 0, 0))


@dataclasses.dataclass(frozen=True)
class IsingModel:
    """Ferromagnetic 2-D Ising model on a periodic H x W lattice.

    State words are {0, 1} (spin s = 2 * word - 1).  MRF convention:
    the Gibbs measure is parameterised directly in natural
    (temperature-absorbed) units,

        log p(s) = beta * sum_<ij> s_i s_j + field * sum_i s_i + const,

    i.e. ``beta`` is the bond coupling J/kT and ``field`` the per-site
    bias h/kT — at beta = 0 the field still acts (i.i.d. spins with
    p(+1) = sigmoid(2 * field)).  The 2-D zero-field critical point sits
    at beta_c = ln(1 + sqrt(2))/2 ~ 0.4407.
    """

    height: int
    width: int
    beta: float = 0.35
    field: float = 0.0

    nbits = 1
    table = None
    supports_fused_gibbs = True

    def __post_init__(self):
        if self.height < 2 or self.width < 2:
            raise ValueError(
                f"lattice must be at least 2x2, got {self.height}x{self.width}"
            )

    # --- gibbs update-rule contract ------------------------------------

    def conditional_logit(self, state: Array) -> Array:
        """Per-site logit of s_i = +1 given the current neighbours:
        2 (beta * neighbour-spin sum + field).

        This bound method is the single conditional implementation — the
        scan executor steps it and the fused kernel traces it (it rides
        a jit static argument, hence the frozen dataclass).
        """
        nb = (
            spin(periodic_roll(state, 1, -2))
            + spin(periodic_roll(state, -1, -2))
            + spin(periodic_roll(state, 1, -1))
            + spin(periodic_roll(state, -1, -1))
        )
        return 2.0 * (self.beta * nb + self.field)

    def update_mask(self, shape: tuple, parity) -> Array:
        """Checkerboard colour active at this half-sweep parity."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 1)
        return ((row + col) % 2) == parity

    def decode(self, words: Array) -> Array:
        return words

    # --- observables ----------------------------------------------------

    def magnetization(self, states: Array) -> Array:
        """Mean spin per lattice: (..., H, W) words -> (...,) in [-1, 1]."""
        s = 2.0 * states.astype(jnp.float32) - 1.0
        return s.mean(axis=(-2, -1))

    def energy(self, states: Array) -> Array:
        """Lattice energy in the measure's natural units — p(s) is
        proportional to exp(-energy(s)), consistent with
        ``conditional_logit``:

            energy(s) = -(beta * sum_<ij> s_i s_j + field * sum_i s_i),

        each periodic bond counted once (right + down neighbours)."""
        s = 2.0 * states.astype(jnp.float32) - 1.0
        bonds = s * jnp.roll(s, -1, -2) + s * jnp.roll(s, -1, -1)
        return -(
            self.beta * bonds.sum(axis=(-2, -1))
            + self.field * s.sum(axis=(-2, -1))
        )

    def random_init(self, key, batch: int) -> Array:
        """Infinite-temperature start: i.i.d. fair spins, (B, H, W)."""
        return jax.random.bernoulli(
            key, 0.5, (batch, self.height, self.width)
        ).astype(jnp.uint32)


def build(
    key,
    randomness: str = "cim",
    backend: str = "auto",
    smoke: bool = False,
    height: int | None = None,
    width: int | None = None,
    batch: int | None = None,
    beta: float | None = None,
    field: float = 0.0,
    n_steps: int | None = None,
    chunk_steps: int = 32,
    num_chains: int = 1,
    collect: str = "all",
):
    """Assemble the Ising workload (see workloads.WorkloadRun).

    ``num_chains`` runs C independent chains in one device program
    (DESIGN.md §Chains-axis); inits are counter-derived per chain —
    ``random_init(chain_key(key, c))`` — so chain c of a C-chain build
    is bit-identical to a solo build, inits included.  ``collect``
    (all | thin:<k> | last, DESIGN.md §Collection) flows to the engine;
    diagnostics consume whatever stream survives.
    """
    from repro import workloads  # deferred: workloads imports this module

    height = height or (8 if smoke else 16)
    width = width or (8 if smoke else 16)
    batch = batch or (2 if smoke else 4)
    n_steps = n_steps or (48 if smoke else 1024)
    model = IsingModel(
        height=height,
        width=width,
        beta=0.35 if beta is None else beta,
        field=field,
    )
    engine = samplers.MHEngine(
        samplers.EngineConfig(
            update="gibbs",
            randomness=randomness,
            execution=backend,
            chunk_steps=chunk_steps,
            num_chains=num_chains,
            collect=collect,
        )
    )
    init = jax.vmap(
        lambda k: model.random_init(k, batch)
    )(samplers.chain_keys(key, num_chains))
    return workloads.WorkloadRun(
        name="ising",
        engine=engine,
        target=model,
        init_words=init[0] if num_chains == 1 else init,
        n_steps=n_steps,
        burn_in=n_steps // 4,
        series_fn=model.magnetization,
        meta={
            "lattice": f"{height}x{width}",
            "batch": batch,
            "num_chains": num_chains,
            "beta": model.beta,
            "field": field,
            "nbits": 1,
            "statistic": "magnetization",
        },
    )
