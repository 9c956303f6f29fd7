"""A cell small enough for the CPU: the served path end to end at toy
widths, for the benchmark's own tests."""

import copy

from bench import harness
from bench import traffic as TR

CONFIG = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "serve": {"max_batch": 4, "max_len": 256, "batch_buckets": [1, 2, 4],
              "kv_block_size": 16, "kv_cache_blocks": 4096,
              "prefix_max_prompt": 128},
}


def sessions() -> dict:
    t = copy.deepcopy(TR.load("sessions"))
    t.update(session_rate_per_s=2.0, grid=16, max_total=256, turns=[2, 3],
             think_mean_s=0.1)
    t["tenants"]["system_prompt_tokens"] = [32, 48, 64, 32]
    t["user"].update(median=16, lo=4, hi=64)
    t["answer"].update(median=6, lo=2, hi=12)
    t["check"] = {"samples": 4, "max_logit_gap": 0.01}
    return t


def unshared() -> dict:
    t = copy.deepcopy(TR.load("chat_unshared"))
    t.update(rate_per_s=4.0, grid=16, max_total=256)
    t["prompt"].update(median=48, lo=16, hi=128)
    t["answer"].update(median=6, lo=2, hi=12)
    t["check"] = {"samples": 4, "max_logit_gap": 0.01}
    return t


def spec(traffic: dict, end_to_end=None, per_layer=None) -> dict:
    conf = copy.deepcopy(CONFIG)
    conf["model"] = harness.model_block(conf)
    bench = harness.load_benchmark()
    return {"cell": {"name": "tiny", "chips": 1}, "config": conf,
            "traffic": traffic,
            "end_to_end": end_to_end if end_to_end is not None
            else bench["end_to_end"],
            "per_layer": per_layer if per_layer is not None
            else bench["per_layer"]}
