"""A toy serving cell for the CPU tests: the serving runner end to end,
with the real cell's configuration and traffic files cut to toy sizes."""
import argparse
import json

import bench_path  # noqa: F401
import run as R

TINY = {"hidden_size": 128, "intermediate_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 4, "vocab_size": 512}


# the family of Qwen1.5-MoE: shared experts beside the routed ones, no
# qk-norm
SHARED = dict(TINY, moe_intermediate_size=64,
              shared_expert_intermediate_size=96, rope_theta=1e6)


def tiny_cell(seed, control=False, sizes=TINY, family=None):
    import jax
    cfg = json.loads((R.BENCH / "configs" / "olmoe-1.3b-6.9b-4L.json")
                     .read_text())
    cfg.update(sizes)
    cfg["run"]["lora_rank"] = 4
    if family:
        cfg["run"].update(registry_id=family, qk_norm=False)
    tp = json.loads((R.BENCH / "traffic" / "decode-tiers.json").read_text())
    tp.update(slots=[[4, 2], [1, 2]], tier_shares=[[4, 1], [1, 1]],
              requests_per_call=12, prompt_lens=[16, 24],
              prompt_len_probs=None, new_tokens=[3, 6],
              new_tokens_probs=None, greedy_share=0.5)
    # a toy model's served tokens are the reference's; its logit rows lie
    # 0.006 from the reference's, the float8 control's 0.07-0.09
    cell = {"check": {"greedy_requests": 2, "sampled_requests": 2,
                      "greedy_tokens": 6, "sampled_tokens": 6},
            "limits": {"mean_logit_gap": 1e-4, "logit_rel_err": 0.02,
                       "nucleus_miss_share": 0.1}}
    args = argparse.Namespace(seed=seed, seconds=0.0, trace=0,
                              control=control)
    entry = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    bench = {"end_to_end": [{"name": n, "unit": "u", "workloads": ["tiny"]}
                            for n in ("gen_tokens_per_s", "setup_s")],
             "per_layer": []}
    ctx = R.Context(args, entry, cfg, tp, cell, jax, jax.devices(),
                    {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return ctx, R.run_cell(ctx, bench)


