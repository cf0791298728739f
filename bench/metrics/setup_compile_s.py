"""Seconds of set-up spent bringing the bucket programs into the executable
cache: the sum of the program's ``exe.compile`` spans (each a trace,
lowering, compile or compile-cache load, kernel autotuning and first
dispatch of one bucket), recorded in set-up.  Nothing when the record holds
no such span."""


def read(rec):
    return rec.get("program", {}).get("spans_s", {}).get("exe.compile")
