"""Seeded model weights that the served int4 block-sparse format holds exactly.

Every weight is made from the seed by integer draws alone, so the same leaf
comes out bit for bit the same wherever it is made: inside the one jitted
call that builds the served model, and layer by layer inside the plain
reference.  Each projection is a whole int4 value times a power of two per
128-row group and output column, with every group holding a +-7 so that its
absmax scale is exact, and with whole 128x128 blocks zeroed in the
block-sparse pattern the served format keeps.  The program's packing of
these weights is therefore lossless, and the reference computes with the
very values the chip serves, in float32.

A projection's sparse layout follows the served format's rule: among the
contraction axis's 128-row blocks, groups of m = 8, 4 or 2 (the first that
divides the block count) keep round(density * m) blocks each, per 128-wide
output tile; the FFN down projection keeps one pattern for every tile.
Where no m divides the block count the projection is dense int4.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

BLOCK = 128
_Q_STD = math.sqrt((15 ** 2 - 1) / 12)     # std of a uniform int in [-7, 7]

# The dense decoder's parameter leaves, by path, and the role each plays
# (families/dense.py; another family brings its own).
ROLES = {
    "embed": "embed",
    "lm_head": "lm_head",
    "ln_f/gamma": "gamma",
    "blocks/ln_attn/gamma": "gamma",
    "blocks/ln_mlp/gamma": "gamma",
    "blocks/attn/wq": "wq",
    "blocks/attn/wk": "wk",
    "blocks/attn/wv": "wv",
    "blocks/attn/wo": "wo",
    "blocks/attn/bq": "bias",
    "blocks/attn/bk": "bias",
    "blocks/attn/bv": "bias",
    "blocks/mlp/gate": "gate",
    "blocks/mlp/up": "up",
    "blocks/mlp/down": "down",
}
PROJECTIONS = ("wq", "wk", "wv", "wo", "gate", "up", "down", "lm_head")


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the high 32 bits are folded in, not
    dropped)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf_key(key: jax.Array, path: str, layer) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
        layer)


def sparse_layout(in_f: int, out_f: int, density: float):
    """(m, k) of the block-sparse layout, or None for a dense projection."""
    if density >= 1.0 or in_f % BLOCK or out_f % BLOCK:
        return None
    n_blocks = in_f // BLOCK
    for m in (8, 4, 2):
        if n_blocks % m == 0 and round(density * m) >= 1:
            return m, int(round(density * m))
    return None


def kept_fraction(in_f: int, out_f: int, density: float) -> float:
    lay = sparse_layout(in_f, out_f, density)
    return 1.0 if lay is None else lay[1] / lay[0]


def projection(key, in_f: int, out_f: int, density: float,
               tile_uniform: bool) -> jax.Array:
    """One (in, out) projection in bfloat16, exactly int4 x 2**e per group."""
    kq, ks, ke, kp = jax.random.split(key, 4)
    q = jax.random.randint(kq, (in_f, out_f), -7, 8, jnp.int32)
    # one +-7 in every (group, column): row (column mod 128) of each group
    rows = jnp.arange(in_f, dtype=jnp.int32)[:, None] % BLOCK
    cols = jnp.arange(out_f, dtype=jnp.int32)[None, :] % BLOCK
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (in_f, out_f)), 7, -7)
    q = jnp.where(rows == cols, sign, q)
    frac = kept_fraction(in_f, out_f, density)
    e0 = round(math.log2(1.0 / (_Q_STD * math.sqrt(in_f * frac))))
    groups = -(-in_f // BLOCK)
    e = e0 - jax.random.randint(ke, (groups, out_f), 0, 2, jnp.int32)
    scale = jnp.ldexp(jnp.ones((), jnp.float32), e)
    scale = jnp.repeat(scale, BLOCK, axis=0)[:in_f]
    w = q.astype(jnp.float32) * scale
    lay = sparse_layout(in_f, out_f, density)
    if lay is not None:
        m, k = lay
        n_groups = in_f // BLOCK // m
        tiles = 1 if tile_uniform else out_f // BLOCK
        bits = jax.random.bits(kp, (n_groups, m, tiles), jnp.uint32)
        rank = jnp.argsort(jnp.argsort(bits, axis=1), axis=1)
        keep = (rank < k).reshape(n_groups * m, tiles)
        keep = jnp.broadcast_to(keep, (n_groups * m, out_f // BLOCK))
        keep = jnp.repeat(jnp.repeat(keep, BLOCK, axis=0), BLOCK, axis=1)
        w = jnp.where(keep, w, 0.0)
    return w.astype(jnp.bfloat16)


def role_leaf(key, role: str, shape: tuple, served: dict) -> jax.Array:
    """One unstacked leaf of the given role, bfloat16."""
    if role == "embed":
        q = jax.random.randint(key, shape, -128, 128, jnp.int32)
        return (q.astype(jnp.float32) * 2.0 ** -6).astype(jnp.bfloat16)
    if role == "gamma":
        q = jax.random.randint(key, shape, -8, 9, jnp.int32)
        return (1.0 + q.astype(jnp.float32) * 2.0 ** -6).astype(jnp.bfloat16)
    if role == "bias":
        q = jax.random.randint(key, shape, -16, 17, jnp.int32)
        return (q.astype(jnp.float32) * 2.0 ** -6).astype(jnp.bfloat16)
    if role in PROJECTIONS:
        in_f, out_f = shape
        return projection(key, in_f, out_f, served["density"][role],
                          role in served.get("tile_uniform", ()))
    raise ValueError(f"no generator for role {role!r}")


def path_str(path) -> str:
    return "/".join(str(getattr(e, "key", e)) for e in path)


def layer_leaf(key, path: str, layer, shape: tuple, served: dict,
               roles: dict = ROLES, generators: dict | None = None):
    """Leaf ``path`` of layer ``layer`` (0 for unstacked leaves).  ``roles``
    maps each parameter path to its role (the dense decoder's by default);
    a role in ``generators`` is made by that function of ``(key, shape,
    served)``, any other by ``role_leaf``."""
    if path not in roles:
        raise KeyError(f"parameter leaf {path!r} has no role in the "
                       "benchmark's generator")
    key = leaf_key(key, path, layer)
    if generators and roles[path] in generators:
        return generators[roles[path]](key, shape, served)
    return role_leaf(key, roles[path], shape, served)


def per_expert(key, n: int, make) -> jax.Array:
    """A leaf stacked over ``n`` experts: expert ``e``'s slice is
    ``make(k)`` with ``e`` folded into ``key``, made one expert at a time."""
    return jax.lax.map(lambda e: make(jax.random.fold_in(key, e)),
                       jnp.arange(n, dtype=jnp.int32))


def build_served(key, shapes, family, model: dict, served: dict, pack):
    """The served parameter tree: every leaf of ``shapes`` (the program's
    ``eval_shape`` of its initialiser) made from ``key`` by the roles of
    ``family`` (its ``ROLES`` and ``GENERATORS``), and the leaves of each of
    its stacked groups of layers (``family.groups(model)``: a top-level key
    of the tree and its layer count) made and packed one layer at a time
    (``lax.map``), all in the one traced function this is called from.
    ``pack`` is the program's packing of a parameter tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [path_str(p) for p, _ in flat]
    groups = family.groups(model)

    def leaf(p, layer, shape):
        return layer_leaf(key, p, layer, shape, served, family.ROLES,
                          family.GENERATORS)

    def group(p):
        head = p.split("/", 1)[0]
        return head if head in groups else None

    out = {}
    for g, n_layers in groups.items():
        stacked = {p: s for p, (_, s) in zip(paths, flat) if group(p) == g}

        def one_layer(layer, stacked=stacked):
            return pack(_nest({p: leaf(p, layer, s.shape[1:])
                               for p, s in stacked.items()}))

        out[g] = jax.lax.map(one_layer, jnp.arange(n_layers,
                                                   dtype=jnp.int32))[g]
    rest = pack(_nest({p: leaf(p, 0, s.shape)
                       for p, (_, s) in zip(paths, flat) if group(p) is None}))
    out = {**rest, **out}
    leaves = []
    for p in paths:
        node = out
        for part in p.split("/"):
            node = node[part]
        leaves.append(node)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for p, v in flat.items():
        node = out
        parts = p.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return out
