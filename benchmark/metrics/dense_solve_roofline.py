"""dense_solve_roofline: the job model's least time for one solve (the
larger of its flops over the peak rate and its bytes over the memory
bandwidth, from benchlib/jobs.py and peaks.json) over the device busy time
per solve of the traced window, in percent."""

from benchlib.jobs import least_time_s


def read(ctx):
    tr, job = ctx["trace"], ctx["job"]
    steps = (ctx["counters"].get("end") or {}).get("done")
    if tr is None or job is None or not steps or tr["busy_s"] <= 0:
        return None
    t_min, _ = least_time_s(job["flops"], job["bytes"], ctx["peaks"])
    return 100.0 * t_min / (tr["busy_s"] / steps)
