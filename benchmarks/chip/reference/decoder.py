"""Plain reference of a Llama-style decoder (Qwen2, SmolLM), from the
published description and nothing of the program.

Hugging Face ``Qwen2ForCausalLM`` / ``LlamaForCausalLM`` as their
``config.json`` states them: token embedding; per layer a pre-norm
(RMSNorm) grouped-query attention with rotary position embeddings
(rotate-half form, ``rope_theta``) and, for Qwen2, biases on the q, k and
v projections; a pre-norm SwiGLU MLP; a final RMSNorm and the LM head
(the embedding, transposed, when ``tie_word_embeddings``).

Everything is float32 with matrix products at ``highest`` precision: on a
TPU a float32 product otherwise runs a single bfloat16 pass.  The whole
sequence runs in one causal pass with no cache, no batching and no
kernels.  ``quant="fp8"`` rounds both operands of every matrix product to
float8 e4m3 (per-tensor scale): the control, one precision step below
the bfloat16 the configurations state.

The benchmark makes the weights (``init_weights``) from the seed in this
module's own layout; the serving driver hands the program the same
numbers rearranged into its parameter tree, and this module draws them
again from the seed when it checks what the program served.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
          "w_gate", "w_up", "w_down")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"d": d, "hq": hq, "hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // hq,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "n_layers": cfg["num_hidden_layers"]}


def weight_shapes(cfg: dict) -> dict:
    """-> {name: shape} of the reference layout (layers stacked on axis 0;
    q/k/v biases only where the configuration has them)."""
    m = dims(cfg)
    d, hq, hkv, dh, f, v, n = (m["d"], m["hq"], m["hkv"], m["dh"], m["f"],
                               m["v"], m["n_layers"])
    shapes = {"embed": (v, d), "final_norm": (d,),
              "ln1": (n, d), "ln2": (n, d),
              "wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
              "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d),
              "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}
    if has_qkv_bias(cfg):
        shapes.update(bq=(n, hq * dh), bk=(n, hkv * dh), bv=(n, hkv * dh))
    if not cfg.get("tie_word_embeddings", False):
        shapes["lm_head"] = (d, v)
    return shapes


def has_qkv_bias(cfg: dict) -> bool:
    return cfg.get("model_type") == "qwen2" or bool(
        cfg.get("attention_bias", False))


def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**64: the low 32 bits seed
    it and the high bits are folded in."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, seed // 2**32)


def init_weights(cfg: dict, key) -> dict:
    """Random weights at the configuration's shapes, float32: embedding
    and biases N(0, 0.02^2), norm scales 1 + N(0, 0.02^2), every other
    matrix N(0, 1/fan_in).  Pure function of ``key``; jit it."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        shape = shapes[name]
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("embed", "bq", "bk", "bv"):
            out[name] = 0.02 * z
        elif name in ("ln1", "ln2", "final_norm"):
            out[name] = 1.0 + 0.02 * z
        else:
            out[name] = z * shape[-2] ** -0.5
    return out


def _q8(x):
    """Round to float8 e4m3 with one per-tensor scale (amax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x (S, H, dh), pos (S,): rotate-half rotary embedding."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def forward(cfg: dict, w: dict, tokens, quant=None):
    """tokens (S,) int32 -> logits (S, vocab) float32, causal."""
    m = dims(cfg)
    hq, hkv, dh = m["hq"], m["hkv"], m["dh"]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    bias = has_qkv_bias(cfg)

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = _mm("sd,de->se", h, p["wq"], quant)
        k = _mm("sd,de->se", h, p["wk"], quant)
        v = _mm("sd,de->se", h, p["wv"], quant)
        if bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = _rope(q.reshape(s, hq, dh), pos, theta)
        k = _rope(k.reshape(s, hkv, dh), pos, theta)
        v = v.reshape(s, hkv, dh)
        g = hq // hkv
        k = jnp.repeat(k, g, axis=1)            # head i reads kv head i//g
        v = jnp.repeat(v, g, axis=1)
        sc = _mm("qhd,khd->hqk", q, k, quant) * dh ** -0.5
        sc = jnp.where(causal[None], sc, -jnp.inf)
        a = jax.nn.softmax(sc, -1)
        o = _mm("hqk,khd->qhd", a, v, quant).reshape(s, hq * dh)
        x = x + _mm("se,ed->sd", o, p["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        gate = _mm("sd,df->sf", h, p["w_gate"], quant)
        up = _mm("sd,df->sf", h, p["w_up"], quant)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["w_down"], quant)
        return x, None

    x = w["embed"][tokens]
    stacked = {k: w[k] for k in LEAVES if k in w}
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _rms(x, w["final_norm"], eps)
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    return _mm("sd,dv->sv", x, head, quant)


def next_token_gaps(logits, tokens):
    """(S, V) reference logits over ``tokens`` (S,) -> (S - 1,): how far
    below row i's best logit lies token i + 1, the one that was served
    after it (0 where it is the reference's argmax)."""
    rows = logits[:-1]
    picked = jnp.take_along_axis(rows, tokens[1:, None], 1)[:, 0]
    return jnp.max(rows, -1) - picked


def control_gaps(logits, control):
    """The same gaps for the tokens a lower-precision forward ``control``
    over the same sequence would put first at each position."""
    rows = logits[:-1]
    pick = jnp.argmax(control[:-1], -1)
    return jnp.max(rows, -1) - jnp.take_along_axis(rows, pick[:, None],
                                                   1)[:, 0]


def cfg_key(cfg: dict) -> tuple:
    """The configuration's scalar entries, hashable (``compiled_check``'s
    cache key)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (bool, int, float, str))))


@functools.lru_cache(maxsize=None)
def compiled_check(cfg_items: tuple, control: bool = False):
    """One jitted check per configuration: (weights, tokens (S,)) ->
    {"served": next_token_gaps, and with ``control`` "control":
    control_gaps of the fp8 forward}.  The caller pads every sequence to
    one length, so it compiles once; padding only follows the positions
    it reads, which a causal pass keeps apart."""
    cfg = dict(cfg_items)

    def check(w, tokens):
        ref = forward(cfg, w, tokens)
        out = {"served": next_token_gaps(ref, tokens)}
        if control:
            out["control"] = control_gaps(ref, forward(cfg, w, tokens,
                                                       "fp8"))
        return out

    return jax.jit(check)
