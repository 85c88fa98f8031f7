"""Tiny cells for the CPU: the cell's own files with the widths cut. The
limits are the tiny size's own (bf16 program against the float32 reference on
the CPU), set as the chip's are: between the program's readings and the
control's."""

import copy

import common


def train_cell():
    cfg = copy.deepcopy(common.load_json(
        common.HERE + "/configs/bert-base.json"))
    cfg.update(hidden_size=64, intermediate_size=128, vocab_size=256,
               num_attention_heads=4, num_hidden_layers=2,
               max_position_embeddings=128)
    cfg["limits"] = {"loss_gap": 2e-3, "grad_norm_gap": 0.05,
                     "grad_diff": 0.07, "change_norm_gap": 0.08}
    mix = copy.deepcopy(common.load_json(
        common.HERE + "/traffic/mlm-train.json"))
    mix.update(batch=8, seq=128, reference_block_rows=4, warm_calls=0)
    return {"name": "tiny.train", "chips": 1, "cfg": cfg, "mix": mix,
            "per_layer": [], "end_to_end": []}


def serve_cell():
    cfg = copy.deepcopy(common.load_json(
        common.HERE + "/configs/gpt2-small.json"))
    cfg.update(n_embd=64, n_head=4, n_layer=2, n_positions=128, n_ctx=128,
               vocab_size=256)
    cfg["limits"] = {"served_logit_gap": 2e-5}
    mix = copy.deepcopy(common.load_json(
        common.HERE + "/traffic/chat-closed.json"))
    mix.update(
        clients=4, pool=16, ramp_seconds=0.5, max_total=128, check_requests=12,
        prompt_len={"dist": "lognormal", "median": 32, "sigma": 0.5,
                    "min": 8, "max": 64},
        new_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 24},
        engine={"max_slots": 4, "page_size": 8, "max_pages_per_seq": 16,
                "max_prompt": 64, "prefix_pages": 0, "spec_k": 0})
    return {"name": "tiny.serve", "chips": 1, "cfg": cfg, "mix": mix,
            "per_layer": [], "end_to_end": []}
