"""Model FLOP/s utilization of the serving step: the FLOPs the model
needs for the ticks of the window (every decoded token with its
attention over its context, and every prefill except the prompt tokens
served from shared prefix pages) over the summed wall time of the ticks
and the chip's bf16 peak.  Taken over tick time, not the window, so an
open loop below capacity still shows how fast a tick is."""
from bench import flops, peaks


def read(record):
    ticks = record.host.get("ticks")
    if not ticks:
        return None
    cfg = record.config
    work = sum(flops.decode_token_flops(cfg, n)
               for lengths in record.host["decode_lengths"] for n in lengths)
    work += sum(flops.prefill_flops(cfg, n, cached)
                for n, cached, _, _ in record.host["prefills"])
    busy = sum(b - a for a, b in ticks)
    return 100.0 * work / busy / peaks.of(record.device_kind)["bf16_flop_s"]
