"""Operations and bytes a serving tick needs, counted from shapes.

These count the algorithm's work, not what any implementation of it does:
the same tick reads the same here whether the step runs on the XLA twins or
on the Pallas kernels.  ``model`` is a configuration's ``model`` section;
``served`` its ``served`` section (per-projection densities of the packed
weights).

A tick is a list of rows ``(length, q)``: the row's resident tokens before
the tick and the tokens it advances by (1 when decoding, a chunk when
prefilling).  Rows with ``q == 0`` do no work.
"""

from __future__ import annotations

from weights import BLOCK, kept_fraction, sparse_layout


def projection_shapes(model: dict) -> dict[str, tuple[int, int]]:
    d, hd = model["d_model"], model["head_dim"]
    hq, hkv, f = model["n_heads"], model["n_kv_heads"], model["d_ff"]
    return {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
            "wo": (hq * hd, d), "gate": (d, f), "up": (d, f),
            "down": (f, d), "lm_head": (d, model["vocab_size"])}


def projection_bytes(in_f: int, out_f: int, density: float) -> int:
    """Packed bytes of one projection: int4 values and bf16 scales of the
    kept blocks, and an int32 block index per kept block and output tile."""
    frac = kept_fraction(in_f, out_f, density)
    kept_rows = int(in_f * frac)
    values = kept_rows * out_f // 2
    scales = (kept_rows // BLOCK) * out_f * 2
    index = 0
    if sparse_layout(in_f, out_f, density) is not None:
        index = (out_f // BLOCK) * (kept_rows // BLOCK) * 4
    return values + scales + index


def layer_weight_bytes(model: dict, served: dict) -> int:
    shapes = projection_shapes(model)
    total = sum(projection_bytes(*shapes[n], served["density"][n])
                for n in ("wq", "wk", "wv", "wo", "gate", "up", "down"))
    d, hd = model["d_model"], model["head_dim"]
    norms = 2 * d * 2
    bias = ((model["n_heads"] + 2 * model["n_kv_heads"]) * hd * 2
            if model["qkv_bias"] else 0)
    return total + norms + bias


def weight_bytes(model: dict, served: dict) -> int:
    """Bytes of the whole packed model as it sits in device memory."""
    d, v = model["d_model"], model["vocab_size"]
    head = projection_bytes(d, v, served["density"]["lm_head"])
    return (model["n_layers"] * layer_weight_bytes(model, served)
            + v * d * 2 + head + d * 2)


def streamed_weight_bytes(model: dict, served: dict) -> int:
    """Bytes a tick must read of the weights: every layer and the head, but
    of the embedding only the rows it looks up (counted per token)."""
    d, v = model["d_model"], model["vocab_size"]
    return (model["n_layers"] * layer_weight_bytes(model, served)
            + projection_bytes(d, v, served["density"]["lm_head"]) + d * 2)


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    return (2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"]
            * dtype_bytes)


def linear_flops_per_token(model: dict, served: dict) -> int:
    """2 x kept multiply-adds of one token through every layer's
    projections (the head is counted per emitting row)."""
    shapes = projection_shapes(model)
    per_layer = sum(
        2 * i * o * kept_fraction(i, o, served["density"][n])
        for n, (i, o) in shapes.items() if n != "lm_head")
    return int(model["n_layers"] * per_layer)


def head_flops(model: dict, served: dict) -> int:
    d, v = model["d_model"], model["vocab_size"]
    return int(2 * d * v * kept_fraction(d, v, served["density"]["lm_head"]))


def attention_flops(model: dict, length: int, q: int) -> int:
    """Scores and weighted values of ``q`` queries at positions
    ``length .. length + q - 1``, each over itself and everything before."""
    keys = q * length + q * (q + 1) // 2
    return (4 * model["n_layers"] * model["n_heads"] * model["head_dim"]
            * keys)


def tick_flops(model: dict, served: dict, rows) -> int:
    live = [(n, q) for n, q in rows if q > 0]
    tokens = sum(q for _, q in live)
    return (tokens * linear_flops_per_token(model, served)
            + len(live) * head_flops(model, served)
            + sum(attention_flops(model, n, q) for n, q in live))


def tick_bytes(model: dict, served: dict, rows) -> int:
    """Weights read once, each live row's resident KV read, the new KV
    written, the looked-up embedding rows read, and one bf16 logits row
    written per live row."""
    live = [(n, q) for n, q in rows if q > 0]
    if not live:
        return 0
    tokens = sum(q for _, q in live)
    kv = kv_bytes_per_token(model)
    d, v = model["d_model"], model["vocab_size"]
    return (streamed_weight_bytes(model, served)
            + sum(n for n, _ in live) * kv
            + tokens * kv
            + tokens * d * 2
            + len(live) * v * 2)


def tick_floor_s(model: dict, served: dict, rows, peaks: dict) -> float:
    """The least time the chip could take for the tick: the larger of its
    operations over peak FLOP/s and its bytes over peak bytes/s."""
    return max(tick_flops(model, served, rows) / peaks["bf16_flops"],
               tick_bytes(model, served, rows) / peaks["hbm_bytes_per_s"])
