"""Parameter and model-FLOP counts of a dense decoder-only transformer,
from the configuration file's sizes alone.

Model FLOPs per trained token follow PaLM (arXiv:2204.02311, App. B):
6 * N_matmul + 12 * L * S * (n_heads * head_dim). N_matmul counts the
weights of every matrix multiplication: the attention projections, the
MLP and the output head, but not the embedding lookup (a gather) and not
the norms. Recomputed operations (remat) are not counted.
"""
from __future__ import annotations


def n_params(cfg: dict) -> int:
    """Every parameter: embedding, head, final norm and the layers."""
    return (cfg["vocab_size"] * cfg["d_model"] + n_head(cfg)
            + cfg["d_model"] + cfg["n_layers"] * _layer_params(cfg))


def n_head(cfg: dict) -> int:
    return 0 if cfg["tie_embeddings"] else cfg["d_model"] * cfg["vocab_size"]


def _layer_params(cfg: dict) -> int:
    return _layer_matmul(cfg) + 2 * cfg["d_model"]


def _layer_matmul(cfg: dict) -> int:
    D, hd = cfg["d_model"], cfg["head_dim"]
    attn = D * hd * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    mlp = (3 if cfg["mlp_gated"] else 2) * D * cfg["d_ff"]
    return attn + mlp


def n_matmul(cfg: dict) -> int:
    return cfg["n_layers"] * _layer_matmul(cfg) + cfg["d_model"] * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq: int) -> float:
    attn = 12 * cfg["n_layers"] * seq * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * n_matmul(cfg) + attn
