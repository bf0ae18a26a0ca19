"""Operations and bytes that the work needs, computed from shapes.

These count what the model and the attention *require*, not what a
kernel happens to execute: ``paged_attention_decode`` scores each query
head against its own KV head only, and reads the live pages once.
"""
from __future__ import annotations


def dense_sizes(cfg: dict) -> dict:
    return {
        "L": int(cfg["num_hidden_layers"]),
        "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "KH": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
    }


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies by in one decoder layer."""
    s = dense_sizes(cfg)
    attn = s["D"] * s["dh"] * (2 * s["H"] + 2 * s["KH"])  # q, o, k, v
    mlp = 3 * s["D"] * s["F"]  # gate, up, down
    return attn + mlp


def attention_flops(cfg: dict, context: int) -> int:
    """QK and PV of one token against ``context`` positions, all layers."""
    s = dense_sizes(cfg)
    return 4 * s["L"] * s["H"] * s["dh"] * int(context)


def decode_token_flops(cfg: dict, context: int) -> int:
    """One decoded token whose attention sees ``context`` positions."""
    s = dense_sizes(cfg)
    mm = s["L"] * layer_matmul_params(cfg) + s["D"] * s["V"]  # + lm head
    return 2 * mm + attention_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int, cached: int = 0) -> int:
    """A prefill of ``prompt_len`` tokens whose first ``cached`` come from
    shared prefix pages (those need no compute), plus one lm-head row."""
    s = dense_sizes(cfg)
    new = max(0, int(prompt_len) - int(cached))
    mm = 2 * s["L"] * layer_matmul_params(cfg) * new
    # token t attends to positions 0..t
    ctx = sum(range(int(cached) + 1, int(prompt_len) + 1))
    return mm + attention_flops(cfg, ctx) + 2 * s["D"] * s["V"]


def paged_attention_need(cfg: dict, lengths, page_tokens: int,
                         kv_bytes: int = 2) -> tuple[int, int]:
    """(flops, bytes) one call of ``paged_attention_decode`` (one layer)
    needs for requests of the given lengths: QK and PV of each query head
    against its own KV head, and one read of each live K/V page, plus the
    queries and outputs."""
    s = dense_sizes(cfg)
    flops = bytes_ = 0
    page = page_tokens * s["KH"] * s["dh"] * kv_bytes
    for n in lengths:
        n = int(n)
        flops += 4 * s["H"] * s["dh"] * n
        bytes_ += 2 * page * -(-n // page_tokens)  # K and V pages
        bytes_ += 2 * s["H"] * s["dh"] * kv_bytes  # q in, out back
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip needs and which bound sets it."""
    t_c = flops / peaks["bf16_flop_s"]
    t_m = bytes_ / peaks["hbm_byte_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
