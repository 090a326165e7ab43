"""The optimizer update's Pallas launches against their roofline (%).

The least time of each traced tick's update is the larger of its bytes over
the peak HBM bandwidth and its FLOPs over the peak (``counts``: only what
the update needs, the ring rows its draws select, never all K rows).  The
share is their sum over the launches' summed device time.
"""


def read(rec):
    launches = rec.reduced.class_count("update")
    seconds = rec.reduced.class_s("update")
    if launches == 0 or seconds <= 0 or not rec.update_costs:
        return None
    least = [
        max(nbytes / rec.peak["hbm_bytes_per_s"], flops / rec.peak["bf16_flops_per_s"])
        for flops, nbytes in rec.update_costs
    ]
    per_launch = sum(least) / len(least)
    return 100.0 * per_launch * launches / seconds
