"""Device time of the prefill programs (``Server._prefill_one``, a jitted
lambda: ``jit__lambda``) in the trace over the admissions the window
made.  Each ``admission`` span runs it once; a trace whose runs do not
match them means the program is no longer found under this name, and is
an error, not a missing reading."""

PROGRAM = "jit__lambda"


def read(record):
    t = record.trace
    if t is None:
        return None
    admissions = t.host_count("admission")
    runs, secs = t.module_stats(lambda name: name == PROGRAM)
    if not admissions or runs != admissions:
        raise RuntimeError(f"{runs} runs of {PROGRAM} in the trace for "
                           f"{admissions} admission spans")
    return 1e3 * secs / admissions
