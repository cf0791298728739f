"""Share (%) of its roofline that the Pallas kernel `qgemm_kernel` reached in
the traced window: the least time its calls could take on this chip (each
call's operations over the int8 peak or its bytes over the memory
bandwidth, whichever is larger, from the shapes in the served program's HLO;
bench/work.py, bench/peaks.json) over the device time of its trace events.
Nothing when the kernel did not run."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    spent = t["kernel_s"].get("qgemm_kernel")
    least = t["kernel_least_s"].get("qgemm_kernel")
    if not spent or not least:
        return None
    return 100.0 * least / spent
