"""deepseek-v2-lite [moe+MLA]: 27L d_model=2048 16H vocab=102400, MLA
kv_lora=512 with no q LoRA (qk_nope=128, qk_rope=64, v=128), MoE: 2 shared +
64 routed top-6 experts d_ff_expert=1408 (softmax scores, greedy top-k, the
top-k weights not renormalised), first layer dense (d_ff=10944).
[HF config.json below; equations: arXiv:2405.04434]

`HF_CONFIG` is the model's published `config.json`, key for key; `CONFIG`
reads its sizes from it.
"""
from repro.configs.base import ArchConfig

SOURCE = ("https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
          "config.json")

HF_CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}

_H = HF_CONFIG
CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=_H["num_hidden_layers"], d_model=_H["hidden_size"],
    n_heads=_H["num_attention_heads"], n_kv_heads=_H["num_key_value_heads"],
    head_dim=_H["v_head_dim"], d_ff=_H["moe_intermediate_size"],
    vocab=_H["vocab_size"], mixer="mla", ffn="moe",
    rope_theta=float(_H["rope_theta"]),
    mla={"kv_lora": _H["kv_lora_rank"], "qk_nope": _H["qk_nope_head_dim"],
         "qk_rope": _H["qk_rope_head_dim"], "v_dim": _H["v_head_dim"]},
    moe={"n_routed": _H["n_routed_experts"],
         "top_k": _H["num_experts_per_tok"],
         "n_shared": _H["n_shared_experts"],
         "d_ff_expert": _H["moe_intermediate_size"],
         "first_dense_layers": _H["first_k_dense_replace"],
         "d_ff_dense": _H["intermediate_size"]},
    source=SOURCE,
)
