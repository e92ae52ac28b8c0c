"""±J spin-glass / MAX-CUT workload — combinatorial optimisation on the
engine (the p-bit coprocessor benchmark family, arXiv:2109.14801).

A 2-D Edwards-Anderson model on a periodic lattice: every bond carries
its own coupling J_ij (bimodal ±J by default), so the landscape is
frustrated and multimodal — the workload class that motivates the
tempering subsystem (repro/tempering): annealing descends to ground
states, replica exchange keeps mixing across the barriers that trap a
single chain.  One site is still one 1-bit compartment word and one
engine step one checkerboard half-sweep; heterogeneous couplings don't
break the two-colour decomposition, but periodic boundaries make the
lattice bipartite only for even H and W, so this model *requires* even
dimensions (the ferromagnetic ``IsingModel`` shares the constraint
implicitly; here frustration makes an odd wrap-around genuinely change
the measure, so it is enforced).

MAX-CUT rides the standard reduction J = -w: the antiferromagnetic
ground state of ``SpinGlass.maxcut`` weights is the maximum cut, and
``cut_value`` converts any spin configuration to its cut weight.
Small instances (H·W <= 20) are exhaustively solvable with
``exhaustive_ground_state`` — the ground-truth anchor the tempering
tests and benches assert against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import samplers

Array = jnp.ndarray


class SpinGlass:
    """2-D spin glass with per-bond couplings on a periodic H x W lattice.

    ``j_right[i, j]`` couples site (i, j) to (i, j+1 mod W);
    ``j_down[i, j]`` couples (i, j) to (i+1 mod H, j).  State words are
    {0, 1} (spin s = 2·word − 1), the measure is natural-units
    (temperature-absorbed) like ``IsingModel``:

        log p(s) = sum_bonds J_ij s_i s_j + field · sum_i s_i + const.

    A plain (identity-hashed) class, not a frozen dataclass — the
    coupling arrays ride jit static arguments by object identity exactly
    like ``TableTarget``.
    """

    nbits = 1
    table = None
    supports_fused_gibbs = True

    def __init__(self, j_right, j_down, field: float = 0.0):
        self.j_right = jnp.asarray(j_right, jnp.float32)
        self.j_down = jnp.asarray(j_down, jnp.float32)
        if (
            self.j_right.ndim != 2
            or self.j_right.shape != self.j_down.shape
        ):
            raise ValueError(
                f"couplings must be two equal (H, W) arrays, got "
                f"{self.j_right.shape} and {self.j_down.shape}"
            )
        self.height, self.width = map(int, self.j_right.shape)
        if (
            self.height < 2 or self.width < 2
            or self.height % 2 or self.width % 2
        ):
            raise ValueError(
                "periodic checkerboard Gibbs needs an even, >= 2x2 lattice "
                f"(odd wrap-around breaks bipartiteness), got "
                f"{self.height}x{self.width}"
            )
        self.field = float(field)
        self.maxcut_reduction = False  # set by the maxcut constructor

    @classmethod
    def bimodal(
        cls, key, height: int, width: int, j: float = 1.0,
        p_ferro: float = 0.5, field: float = 0.0,
    ) -> "SpinGlass":
        """±J couplings: each bond is +j with prob ``p_ferro``, else -j."""
        k_r, k_d = jax.random.split(key)

        def sign(k):
            planes = jax.random.bernoulli(k, p_ferro, (height, width))
            return 2.0 * planes.astype(jnp.float32) - 1.0

        return cls(j * sign(k_r), j * sign(k_d), field=field)

    @classmethod
    def maxcut(
        cls, key, height: int, width: int, max_weight: int = 3,
        signed: bool = True,
    ) -> "SpinGlass":
        """(Signed) MAX-CUT on the lattice graph: J = -w, zero field,
        ``cut_value`` enabled.  Integer weight magnitudes in
        [1, max_weight]; ``signed`` draws a random sign per edge —
        essential for a non-trivial instance, because the even periodic
        lattice graph is bipartite and unsigned MAX-CUT on a bipartite
        graph is trivially the checkerboard partition."""
        k_r, k_d, k_sr, k_sd = jax.random.split(key, 4)

        def weights(k_mag, k_sign):
            w = jax.random.randint(
                k_mag, (height, width), 1, max_weight + 1
            ).astype(jnp.float32)
            if signed:
                flip = jax.random.bernoulli(k_sign, 0.5, (height, width))
                w = jnp.where(flip, -w, w)
            return w

        model = cls(-weights(k_r, k_sr), -weights(k_d, k_sd), field=0.0)
        model.maxcut_reduction = True
        return model

    # --- gibbs update-rule contract ------------------------------------
    #
    # One math body serves both executors: the scan step calls
    # ``conditional_logit`` (couplings closed over), the fused kernel
    # traces ``fused_logit`` with the couplings as ``fused_consts``
    # operands — kernel traces cannot capture array closures
    # (DESIGN.md §Tempering).

    @property
    def fused_consts(self) -> tuple:
        return (self.j_right, self.j_down)

    def fused_logit(self, state: Array, j_right, j_down) -> Array:
        """Per-site logit of s_i = +1 given the neighbours:
        2 (sum_j J_ij s_j + field), each incident bond with its own J."""
        # {0, 1} words via int32: Mosaic has no uint32 -> float32 cast
        s = 2.0 * state.astype(jnp.int32).astype(jnp.float32) - 1.0
        nb = (
            j_right * jnp.roll(s, -1, -1)
            + jnp.roll(j_right, 1, -1) * jnp.roll(s, 1, -1)
            + j_down * jnp.roll(s, -1, -2)
            + jnp.roll(j_down, 1, -2) * jnp.roll(s, 1, -2)
        )
        return 2.0 * (nb + self.field)

    def conditional_logit(self, state: Array) -> Array:
        return self.fused_logit(state, self.j_right, self.j_down)

    def update_mask(self, shape: tuple, parity) -> Array:
        """Checkerboard colour active at this half-sweep parity."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 1)
        return ((row + col) % 2) == parity

    def decode(self, words: Array) -> Array:
        return words

    # --- observables / optimisation ------------------------------------

    def energy(self, states: Array) -> Array:
        """Natural-units energy, p ∝ exp(-E), each bond counted once:
        E(s) = -(sum J_r s s_right + sum J_d s s_down + field sum s)."""
        s = 2.0 * states.astype(jnp.float32) - 1.0
        bonds = (
            self.j_right * s * jnp.roll(s, -1, -1)
            + self.j_down * s * jnp.roll(s, -1, -2)
        )
        return -(
            bonds.sum(axis=(-2, -1)) + self.field * s.sum(axis=(-2, -1))
        )

    def cut_value(self, states: Array) -> Array:
        """Cut weight of the ±1 partition under the MAX-CUT reduction
        w = -J (requires antiferromagnetic couplings and zero field):
        cut(s) = (W_total - E(s)) / 2, maximal at the ground state."""
        if not self.maxcut_reduction or self.field != 0.0:
            raise ValueError(
                "cut_value needs a zero-field MAX-CUT model "
                "(use SpinGlass.maxcut)"
            )
        w_total = -(self.j_right.sum() + self.j_down.sum())
        return 0.5 * (w_total - self.energy(states))

    def random_init(self, key, batch: int) -> Array:
        """Infinite-temperature start: i.i.d. fair spins, (B, H, W)."""
        return jax.random.bernoulli(
            key, 0.5, (batch, self.height, self.width)
        ).astype(jnp.uint32)


def exhaustive_ground_state(
    model: SpinGlass, chunk: int = 1 << 14
) -> tuple[float, np.ndarray]:
    """Brute-force (ground energy, one ground state) for H·W <= 20 sites
    — the exact anchor for annealing/tempering correctness tests."""
    n = model.height * model.width
    if n > 20:
        raise ValueError(
            f"exhaustive enumeration capped at 20 sites, got {n}"
        )
    bit = np.arange(n, dtype=np.int64)
    best_e = np.inf
    best_state = None
    for start in range(0, 1 << n, chunk):
        words = np.arange(start, min(start + chunk, 1 << n), dtype=np.int64)
        states = ((words[:, None] >> bit) & 1).astype(np.uint32).reshape(
            -1, model.height, model.width
        )
        e = np.asarray(model.energy(jnp.asarray(states)))
        i = int(np.argmin(e))
        if e[i] < best_e:
            best_e = float(e[i])
            best_state = states[i]
    return best_e, best_state


def build(
    key,
    randomness: str = "cim",
    backend: str = "auto",
    smoke: bool = False,
    height: int | None = None,
    width: int | None = None,
    batch: int | None = None,
    j: float = 1.0,
    p_ferro: float = 0.5,
    field: float = 0.0,
    maxcut: bool = False,
    n_steps: int | None = None,
    chunk_steps: int = 32,
    num_chains: int = 1,
    collect: str = "all",
):
    """Assemble the spin-glass workload (see workloads.WorkloadRun).

    The plain WorkloadRun samples the glass at fixed couplings (the
    energy series feeds the chain diagnostics); the ground-state hunt is
    the tempering subsystem's job — ``launch/sample --ladder/--anneal``
    wraps this same target.  ``maxcut`` swaps the ±J bimodal couplings
    for a signed MAX-CUT instance (J = -w, ``cut_value`` enabled).
    Couplings come from a dedicated split of the build key; inits stay
    counter-derived per chain (``random_init(chain_key(k, c))``) so
    chain 0 of a C-chain build is bit-identical to a solo build,
    matching the other zoo builders.
    """
    from repro import workloads  # deferred: workloads imports this module

    height = height or (4 if smoke else 8)
    width = width or (4 if smoke else 8)
    batch = batch or (2 if smoke else 4)
    n_steps = n_steps or (48 if smoke else 768)
    k_bonds, k_init = jax.random.split(key)
    if maxcut:
        model = SpinGlass.maxcut(k_bonds, height, width)
    else:
        model = SpinGlass.bimodal(
            k_bonds, height, width, j=j, p_ferro=p_ferro, field=field
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
    )(samplers.chain_keys(k_init, num_chains))
    return workloads.WorkloadRun(
        name="spin_glass",
        engine=engine,
        target=model,
        init_words=init[0] if num_chains == 1 else init,
        n_steps=n_steps,
        burn_in=n_steps // 4,
        series_fn=model.energy,
        meta={
            "lattice": f"{height}x{width}",
            "batch": batch,
            "num_chains": num_chains,
            "maxcut": maxcut,
            "j": j,
            "p_ferro": p_ferro,
            "field": field,
            "nbits": 1,
            "statistic": "energy",
        },
    )
