"""Small cells of the benchmark for CPU tests: the same harness, drivers
and references as the chip cells, at sizes a test run can hold."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def tiny_chat_cell(rate_rps=5.0):
    """qwen3-4b.chat with the model's widths cut for a CPU test (bf16)."""
    from bench import run

    cfg = read("bench/configs/qwen3-4b.json")
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, intermediate_size=256, vocab_size=512,
               num_hidden_layers=2)
    mix = read("bench/traffic/chat.json")
    mix["system_prompts"]["lengths"] = [32, 64, 32, 64]
    mix["user_turn"]["lengths"] = [16, 32]
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 16}
    settings = read("bench/cells/qwen3-4b.chat.json")
    settings.update(rate_rps=rate_rps, batch=4, cache_len=128,
                    pool_pages=64, check_requests=4, check_tokens=16)
    return {"name": "qwen3-4b.chat", "chips": 1, "config": cfg, "mix": mix,
            "settings": settings,
            "metrics": run.metrics_of(read("BENCHMARK.json"),
                                      "qwen3-4b.chat")}
