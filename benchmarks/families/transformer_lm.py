"""The ``transformer_lm`` family: the repo's decoder-only LM
(``distlearn_tpu.models.transformer.transformer_lm``) built from a
configuration file in GPT-2's key names, its weights made on the device from
the seed, its parameter tree renamed into the plain reference's layout, and
the analytic count of the operations its forward and backward passes need.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distlearn_tpu.models.transformer import transformer_lm

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  ``n_inner`` null means GPT-2's 4 x ``n_embd``."""
    dim = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * dim
    if inner % dim:
        raise ValueError(f"n_inner={inner} is not a multiple of n_embd={dim}")
    return transformer_lm(
        vocab=cfg["vocab_size"], dim=dim, depth=cfg["n_layer"],
        heads=cfg["n_head"], mlp_ratio=inner // dim,
        max_len=max_len or cfg["n_positions"],
        compute_dtype=_DTYPES[compute_dtype], scan_blocks=scan_blocks,
        remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32."""
    return jax.jit(lambda k: model.init(k)[0], out_shardings=sharding)(key)


def param_count(cfg: dict) -> int:
    e, f = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    layer = 4 * e * e + 2 * e * f + f + 3 * e     # wq wk wv wo, mlp, 2 norms
    return (cfg["vocab_size"] + cfg["n_positions"]) * e + e \
        + cfg["n_layer"] * layer


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE for one sequence
    of ``seq`` tokens (a multiply-add is 2): matrix products and attention
    only, causal attention counted at half the square, backward = 2 x
    forward, recomputation not counted (the PaLM-appendix count)."""
    e, f = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    per_token = cfg["n_layer"] * (8 * e * e + 4 * e * f) \
        + 2 * e * cfg["vocab_size"]
    attention = cfg["n_layer"] * 2 * seq * seq * e     # QK^T and PV, causal
    return 3.0 * (seq * per_token + attention)


_LAYER_KEYS = {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo", "w1": "w_fc",
               "b1": "b_fc", "w2": "w_proj", "b2": "b_proj"}


def to_reference(params) -> dict:
    """The system's tree (scanned ``blocks`` or per-block ``block<i>``) in
    the reference's layout and names, one dict a layer.  Leaves that are
    already float32 and unsliced SHARE the system's buffers: drop the system's
    tree before handing this one to a reference that donates it."""
    def layer(blk):
        out = {v: jnp.asarray(blk[k], jnp.float32)
               for k, v in _LAYER_KEYS.items()}
        out["ln_1"] = jnp.asarray(blk["ln1"]["scale"], jnp.float32)
        out["ln_2"] = jnp.asarray(blk["ln2"]["scale"], jnp.float32)
        return out
    if "blocks" in params:
        depth = params["blocks"]["wq"].shape[0]
        blocks = [jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                  for i in range(depth)]
    else:
        depth = sum(1 for k in params if k.startswith("block"))
        blocks = [params[f"block{i}"] for i in range(depth)]
    return {"wte": jnp.asarray(params["embed"], jnp.float32),
            "wpe": jnp.asarray(params["pos"], jnp.float32),
            "ln_f": jnp.asarray(params["out_norm"]["scale"], jnp.float32),
            "layers": [layer(b) for b in blocks]}
