"""The sampling step's share of its roofline over a batch window, in %.

The least time the window's jobs could take is the larger of two legs:
the Threefry blocks the fused stream contract requires, over the block
rate the traced run measured on the calibration kernel
(``calibrate.py``), and the bytes the jobs must move, over the chip's
HBM peak (``peaks.json``).  The share is that least time over the
device's busy time in the window, whatever executor ran the step.
"""


def share(r):
    res = r.result
    blocks = getattr(res, "job_blocks", None)
    if blocks is None or not r.calib_blocks_per_s or r.peak is None:
        return None
    jobs = res.traced_jobs
    t_blocks = jobs * blocks / r.calib_blocks_per_s
    t_bytes = jobs * res.job_bytes / r.peak["hbm_bytes_per_s"]
    leg = "threefry" if t_blocks >= t_bytes else "hbm"
    r.log(f"roofline: {jobs} jobs, threefry leg {t_blocks:.6f} s, hbm leg "
          f"{t_bytes:.6f} s, bound by {leg}; busy {r.trace.busy_s:.6f} s")
    return 100.0 * max(t_blocks, t_bytes) / r.trace.busy_s
