"""Compile each cell's device programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python benchmarks/chip/aot_compile.py [cell ...]

A rehearsal before chip time: the TPU compiler, installed here, refuses
what the chip would refuse (a kernel's tiling, fast memory, a program
larger than the device's memory) and reports each program's memory.
Per cell it compiles, at the cell's own sizes:

* batch, ``compiled``: the engine's one-dispatch job program;
* batch, direct entry on the MH Pallas path: one chunk's kernel (fused,
  or the operand kernel plus the randomness backend's chunk program);
* serve: the scheduler's class advance program for one segment.

and prints ``memory_analysis()`` of each.  Nothing runs.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import lib  # noqa: E402
import run_cell  # noqa: E402


def _sds(shape, dtype, chip):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)


def _report(label: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{label}: arguments {m.argument_size_in_bytes} B, outputs "
          f"{m.output_size_in_bytes} B, temporaries {m.temp_size_in_bytes} "
          f"B, aliased {m.alias_size_in_bytes} B; total {total} B "
          f"({total / 2**30:.3f} GiB)", flush=True)


def batch_programs(c, chip) -> None:
    import jax
    import jax.numpy as jnp

    from repro import workloads
    from repro.kernels import rng
    from repro.kernels.mh.mh import mh_chain_pallas, mh_chain_pallas_fused
    from repro.samplers import plan as plan_mod

    tr = c.traffic
    wl = workloads.build(
        c.cfg["workload"], jax.random.PRNGKey(0),
        randomness=tr["randomness"], backend=tr["execution"],
        chunk_steps=int(tr["chunk_steps"]), collect=tr["collect"],
        n_steps=int(tr["n_steps"]), **c.cfgmod.builder_kwargs(c.cfg),
        **tr.get("builder", {}),
    )
    init = wl.init_words
    if tr["compiled"]:
        lowered = plan_mod._submit_compiled.lower(
            _sds((2,), jnp.uint32, chip), _sds(init.shape, init.dtype, chip),
            engine=wl.engine, target=wl.target, n_steps=wl.n_steps,
            chain_id=0, step0=0, collect=None, mesh=None,
        )
        _report(f"{c.cell['name']}: job program", lowered.compile())
        return
    b, cc = init.shape
    k = int(tr["chunk_steps"])
    v = wl.target.table.shape[-1]
    if tr["randomness"] == "fused":
        def kernel(table, words, k0, k1, t0):
            return mh_chain_pallas_fused(
                table, words, k0, k1, t0, nbits=wl.target.nbits, n_steps=k,
                cc=cc, p_u32=rng.threshold_u32(0.45), block_c=256,
                interpret=False)

        compiled = jax.jit(kernel).lower(
            _sds((b, v), jnp.float32, chip), _sds((b, cc), jnp.uint32, chip),
            _sds((cc,), jnp.uint32, chip), _sds((cc,), jnp.uint32, chip),
            _sds((cc,), jnp.int32, chip)).compile()
        _report(f"{c.cell['name']}: fused MH chunk kernel", compiled)
        return

    def kernel(table, words, flips, u):
        return mh_chain_pallas(table, words, flips, u, nbits=wl.target.nbits,
                               block_c=256, interpret=False)

    compiled = jax.jit(kernel).lower(
        _sds((b, v), jnp.float32, chip), _sds((b, cc), jnp.uint32, chip),
        _sds((k, b, cc), jnp.uint32, chip),
        _sds((k, b, cc), jnp.float32, chip)).compile()
    _report(f"{c.cell['name']}: operand MH chunk kernel", compiled)
    backend = wl.engine.randomness

    def operands(key, start):
        return backend.chunk(key, start, k, (b, cc), wl.target.nbits)

    compiled = jax.jit(operands).lower(
        _sds((2,), jnp.uint32, chip), _sds((), jnp.int32, chip)).compile()
    _report(f"{c.cell['name']}: {tr['randomness']} operand chunk", compiled)


def serve_programs(c, chip) -> None:
    import jax.numpy as jnp

    from repro.serving import Scheduler

    tr = c.traffic
    sched = Scheduler(
        n_slots=int(tr["slots"]), randomness=tr["randomness"],
        execution=tr["execution"], smoke=False,
        chunk_steps=int(tr["chunk_steps"]),
        workload_kwargs={**c.cfgmod.builder_kwargs(c.cfg),
                         **tr.get("builder", {})},
    )
    ex = sched.executor_for(c.cfg["workload"])
    n = ex.n_slots
    lowered = ex._advance.lower(
        _sds(ex.words.shape, jnp.uint32, chip),
        _sds(ex.logp.shape, jnp.float32, chip),
        _sds((n, 2), jnp.uint32, chip), _sds((n,), jnp.int32, chip),
        _sds((n,), jnp.int32, chip), seg=int(tr["chunk_steps"]),
        collect=tr["collect"],
    )
    _report(f"{c.cell['name']}: class advance program", lowered.compile())


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = lib.load_json(run_cell.ROOT / "BENCHMARK.json")
    names = (argv if argv else None) or [w["name"] for w in bench["workloads"]]
    for name in names:
        c = run_cell.load_cell(name)
        if c.traffic["kind"] == "serve":
            serve_programs(c, chip)
        else:
            batch_programs(c, chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
