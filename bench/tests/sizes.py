"""The benchmark's cells cut to a size the CPU runs in seconds: the same
files, with every width and count made small.  For tests only."""
import copy

from bench import harness


def bench():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def small_qwen(name="qwen3-4b"):
    c = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    c["model"].update(hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=256)
    c["replica"].update(slots=4, capacity=256, num_pages=48,
                        prefill_chunk_tokens=32)
    return c


def small_mamba():
    c = harness.load_json(harness.BENCH / "configs" / "mamba2-780m.json")
    c["model"].update(d_model=64, n_layer=2, vocab_size=256, d_state=16,
                      headdim=16, chunk_size=16)
    c["replica"].update(slots=4, capacity=256, prefill_chunk_tokens=32)
    return c


def small_sessions(mix="sessions"):
    s = harness.load_json(harness.BENCH / "traffic" / f"{mix}.json")
    s = copy.deepcopy(s)
    s.update(system_prompt_tokens=64, max_prompt_tokens=192,
             session_rate_per_s=1.0, lead_in_s=1.0, drain_s=60.0)
    s["user_tokens"].update(median=24, max=64)
    s["output_tokens"].update(median=10, min=4, max=24)
    s["limits"] = {"ttft_ms": 60000.0, "tpot_ms": 5000.0}
    s["warmup"] = {"horizon_s": 10.0, "requests": 3}
    return s
