"""The dense decoder family: pre-norm RMSNorm, rotary positions,
grouped-query attention with an optional q/k/v bias, SwiGLU FFN, untied
head.  A configuration with ``"family": "dense"`` is served, checked and
counted by what this module names, all of it kept in the benchmark's
general modules.

A family module gives the harness (``bench.FAMILY_NEEDS``):

- ``ROLES``, each parameter path of the program's tree -> its role, and
  ``GENERATORS``, each role of the family's own -> the function of ``(key,
  shape, served)`` that makes its leaf (``weights.role_leaf`` makes the
  rest); ``groups(model)``, each stacked group of layers (a top-level key
  of the tree) -> its layer count;
- ``program_sizes(cfg)``, each size the configuration's ``model`` section
  states -> the program's value of it;
- ``logits``, the plain reference that ``check.gaps`` runs, float8
  control included;
- ``tick_flops``, ``tick_bytes``, ``tick_floor_s``, ``weight_bytes`` and
  ``kv_bytes_per_token``, the counts the step metrics read.
"""
import counts
import reference
import weights

ROLES = weights.ROLES
GENERATORS: dict = {}


def groups(model: dict) -> dict[str, int]:
    return {"blocks": model["n_layers"]}


def program_sizes(cfg) -> dict:
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "qkv_bias": cfg.qkv_bias,
            "rope_theta": cfg.rope_theta}


logits = reference.logits
tick_flops = counts.tick_flops
tick_bytes = counts.tick_bytes
tick_floor_s = counts.tick_floor_s
weight_bytes = counts.weight_bytes
kv_bytes_per_token = counts.kv_bytes_per_token
