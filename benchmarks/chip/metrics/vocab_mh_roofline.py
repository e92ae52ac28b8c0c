"""Roofline share of the vocabulary-width MH kernel, in %: its least time
over its device time in the traced window.

The kernel is found by the name the program gives it
(``mh_vocab_window``).  Its least time is the larger of two legs: the
Threefry blocks it must draw (``kernel_blocks`` of the configuration,
over the block rate the traced run measured, ``calibrate.py``) and the
bytes it must move (``kernel_bytes``: one table entry per chain-step and
the chains' state, over the HBM peak, ``peaks.json``), for every job the
profiler covered.  None where no such kernel ran."""

KERNEL = "mh_vocab_window"


def read(r):
    res = r.result
    blocks = getattr(res, "kernel_blocks", None)
    kernel = sum(s for n, s in r.trace.ops.items() if KERNEL in n)
    if blocks is None or kernel == 0.0 or not r.calib_blocks_per_s:
        return None
    jobs = res.traced_jobs
    t_blocks = jobs * blocks / r.calib_blocks_per_s
    t_bytes = jobs * res.kernel_bytes / r.peak["hbm_bytes_per_s"]
    r.log(f"{KERNEL}: {jobs} jobs, {kernel:.6f} s; threefry leg "
          f"{t_blocks:.6f} s, hbm leg {t_bytes:.6f} s")
    return 100.0 * max(t_blocks, t_bytes) / kernel
