"""Device time of one paged decode step: the summed device time of the
decode program (``jit_step``, ``launch/serve.py``
``_paged_decode_views_fn``) in the trace over its runs.  Each ``decode``
span of the window runs it once; a trace whose runs do not match them
means the program is no longer found under this name, and is an error,
not a missing reading."""

PROGRAM = "jit_step"


def read(record):
    t = record.trace
    if t is None:
        return None
    runs, secs = t.module_stats(lambda name: name == PROGRAM)
    decodes = t.host_count("decode")
    if not decodes or runs != decodes:
        raise RuntimeError(f"{runs} runs of {PROGRAM} in the trace for "
                           f"{decodes} decode spans")
    return 1e3 * secs / runs
