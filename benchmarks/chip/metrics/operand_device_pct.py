"""Device time outside the Pallas sampling kernels, over busy time, in %.

Under cim randomness that time is the randomness model building the
proposal and uniform operands; under fused randomness the kernels draw
their own, and what is left is the engine's chunk writes and glue.
Kernels are found by the names the program gives its Pallas kernels.
Read where the sampling step ran as a Pallas kernel."""

KERNELS = ("_mh_kernel", "_mh_fused_kernel", "_gibbs_kernel",
           "_gibbs_fused_kernel", "mh_chain_pallas", "gibbs_chain_pallas")


def read(r):
    ops = r.trace.ops
    kernel = sum(s for n, s in ops.items() if any(k in n for k in KERNELS))
    if kernel == 0.0:
        return None
    return 100.0 * (1.0 - kernel / r.trace.busy_s)
