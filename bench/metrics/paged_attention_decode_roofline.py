"""Share of its roofline that ``paged_attention_decode`` reaches: the
least time the chip needs for the work the decode attention requires
(each query head against its own KV head, each live K/V page read once;
``bench.flops.paged_attention_need``), summed over every call in the
window, over the summed device time of the kernel's calls in the trace.
Each call is bound by memory at these sizes; the bound is taken per call
as the larger of the compute and the memory time."""
from bench import flops, peaks

KERNEL = "%paged_attention_decode"


def read(record):
    t = record.trace
    lengths = record.host.get("decode_lengths")
    if t is None or not lengths:
        return None
    spent = t.op_seconds(lambda name: name.startswith(KERNEL))
    if spent <= 0:
        return None
    cfg, peak = record.config, peaks.of(record.device_kind)
    page = int(record.settings["page_tokens"])
    need = 0.0
    for call in lengths:
        f, b = flops.paged_attention_need(cfg, call, page)
        need += flops.roofline_s(f, b, peak)[0]
    need *= int(cfg["num_hidden_layers"])  # one call a layer
    return 100.0 * need / spent
