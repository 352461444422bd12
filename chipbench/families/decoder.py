"""The pre-norm decoder: grouped-query rotary attention (half-split RoPE),
then a SwiGLU MLP or top-k SwiGLU experts with the program's capacity rule
(``int(1.25 · group · k / E)`` per group of up to 512 tokens within a row,
later choices dropped), in every layer; a head over the padded vocabulary,
tied or not. Loss: next-token cross entropy plus the MoE load-balance and
router-z terms. FLOPs per token: attention projections, causal scores and
values (half the context on average), the MLP or the router and k experts,
the head over the real vocabulary; not norms, softmaxes, recompute, MoE
dispatch, capacity padding, the embedding gather or padded vocabulary.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference import HIGHEST, _activation, _matmul, _rms, _rope

VOCAB_PAD = 256
MOE_GROUP = 512

KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "param_dtype": "param_dtype",
    "moe_capacity_factor": "capacity_factor",
    "moe_aux_loss_coef": "aux_loss_coef",
    "moe_z_loss_coef": "router_z_coef",
}
PROGRAM_ONLY_KEYS = {"compute_dtype": "compute_dtype"}


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes and rules the reference needs, as the configuration runs them."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    num_experts: int = 0
    experts_per_token: int = 0
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    param_dtype: str = "float32"
    capacity_factor: float = 1.25
    aux_loss_coef: float = 1e-2
    router_z_coef: float = 1e-3

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // VOCAB_PAD) * VOCAB_PAD

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0


def model(config: dict) -> Model:
    return Model(**{f: config[k] for k, f in KEYS.items() if k in config})


def _linear(key, d_in, d_out, dtype):
    """Weights by the program's key schedule, drawn in float32, stored in param dtype."""
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (1.0 / math.sqrt(d_in))
    return {"w": w.astype(dtype)}


def _block(key, m: Model, dtype):
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 4)
    d, h, kv, hd = m.d_model, m.num_heads, m.num_kv_heads, m.head_dim
    p = {
        "norm1": {"scale": jnp.ones((d,), dtype)},
        "attn": {
            "wq": _linear(ka[0], d, h * hd, dtype),
            "wk": _linear(ka[1], d, kv * hd, dtype),
            "wv": _linear(ka[2], d, kv * hd, dtype),
            "wo": _linear(ka[3], h * hd, d, dtype),
        },
        "norm2": {"scale": jnp.ones((d,), dtype)},
    }
    if m.is_moe:
        km = jax.random.split(ks[2], 4)
        e, f = m.num_experts, m.d_ff
        scale_in = 1.0 / jnp.sqrt(d)
        scale_out = 1.0 / jnp.sqrt(f)
        p["moe"] = {
            "router": _linear(km[0], d, e, dtype),
            "w_in": (jax.random.normal(km[1], (e, d, f), jnp.float32) * scale_in).astype(dtype),
            "w_out": (jax.random.normal(km[2], (e, f, d), jnp.float32) * scale_out).astype(dtype),
            "w_gate": (jax.random.normal(km[3], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        }
    else:
        km = jax.random.split(ks[2], 3)
        p["mlp"] = {
            "in": _linear(km[0], d, m.d_ff, dtype),
            "out": _linear(km[1], m.d_ff, d, dtype),
            "gate": _linear(km[2], d, m.d_ff, dtype),
        }
    return p


def init_params(m: Model, key) -> dict:
    dtype = jnp.dtype(m.param_dtype)
    ks = jax.random.split(key, 5)
    table = jax.random.normal(ks[0], (m.padded_vocab, m.d_model), jnp.float32) * 0.02
    layer_keys = jax.random.split(jax.random.fold_in(ks[1], 0), m.num_layers)
    p = {
        "embed": {"table": table.astype(dtype)},
        "final_norm": {"scale": jnp.ones((m.d_model,), dtype)},
        "blocks": [jax.vmap(lambda k: _block(k, m, dtype))(layer_keys)],
    }
    if not m.tie_embeddings:
        p["head"] = _linear(ks[2], m.d_model, m.padded_vocab, dtype)
    return p


def _attention(p, m: Model, h, mm):
    s = h.shape[0]
    hq, hkv, hd = m.num_heads, m.num_kv_heads, m.head_dim
    q = _rope(mm("sd,de->se", h, p["wq"]["w"]).reshape(s, hq, hd), m.rope_theta)
    k = _rope(mm("sd,de->se", h, p["wk"]["w"]).reshape(s, hkv, hd), m.rope_theta)
    v = mm("sd,de->se", h, p["wv"]["w"]).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    qb = min(s, 1024)

    @jax.checkpoint
    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = mm("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        causal = jnp.arange(s)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", pr, v)

    out = lax.map(block, jnp.arange(s // qb)).reshape(s, hq * hd)
    return mm("se,ed->sd", out, p["wo"]["w"])


def _swiglu(x, w_gate, w_in, w_out, mm, eq_in, eq_out):
    return mm(eq_out, jax.nn.silu(mm(eq_in, x, w_gate)) * mm(eq_in, x, w_in), w_out)


def _moe(p, m: Model, h, mm):
    """Top-k experts with per-group capacity; returns (out, aux, z)."""
    s, d = h.shape
    g = min(MOE_GROUP, s)
    x = h.reshape(s // g, g, d)
    e, k = m.num_experts, m.experts_per_token
    cap = max(int(m.capacity_factor * g * k / e), k)
    logits = mm("bsd,de->bse", x, p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(probs, k)
    gate = top / jnp.maximum(jnp.sum(top, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (b, g, k, e)
    flat = onehot.reshape(x.shape[0], g * k, e)
    before = (jnp.cumsum(flat, axis=1) - flat).reshape(onehot.shape)
    kept = jnp.sum(before * onehot, axis=-1) < cap  # (b, g, k)
    weight = jnp.einsum("bsk,bske->bse", gate * kept, onehot, precision=HIGHEST)
    y = _swiglu(x, p["w_gate"], p["w_in"], p["w_out"], mm, "bsd,edf->bsef", "bsef,efd->bsed")
    out = jnp.einsum("bse,bsed->bsd", weight, y, precision=HIGHEST)
    frac = jnp.mean(jnp.sum(onehot, axis=2) / k, axis=1)
    aux = e * jnp.mean(jnp.sum(frac * jnp.mean(probs, axis=1), axis=-1))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out.reshape(s, d), aux, z


def row_loss(params, m: Model, tokens, labels, precision: str = "f32"):
    """Loss of one row of ``tokens``/``labels`` (S,), everything in float32."""
    mm, act = _matmul(precision), _activation(precision)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    table = params["embed"]["table"].astype(jnp.float32)
    x = act(table[tokens])
    blocks = f32(params["blocks"][0])
    aux = z = jnp.float32(0.0)
    for layer in range(m.num_layers):
        bp = jax.tree.map(lambda a, i=layer: a[i], blocks)
        x = act(x + _attention(bp["attn"], m, act(_rms(x, bp["norm1"]["scale"])), mm))
        h = act(_rms(x, bp["norm2"]["scale"]))
        if m.is_moe:
            y, a, zz = _moe(bp["moe"], m, h, mm)
            aux, z = aux + a, z + zz
        else:
            mp = bp["mlp"]
            y = _swiglu(h, mp["gate"]["w"], mp["in"]["w"], mp["out"]["w"], mm, "sd,df->sf", "sf,fd->sd")
        x = act(x + y)
    x = act(_rms(x, params["final_norm"]["scale"].astype(jnp.float32)))
    if m.tie_embeddings:
        logits = mm("sd,vd->sv", x, table)
    else:
        logits = mm("sd,dv->sv", x, params["head"]["w"].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    total = ce
    if m.is_moe:
        total = total + m.aux_loss_coef * aux + m.router_z_coef * z
    return total


def forward_flops_per_token(m: dict, seq: int) -> float:
    d = m["hidden_size"]
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f = m["intermediate_size"]
    proj = 2 * d * (2 * hq * hd + 2 * hkv * hd)
    scores = 2 * 2 * hq * hd * (seq / 2)
    if m.get("num_local_experts"):
        e, k = m["num_local_experts"], m["num_experts_per_tok"]
        ffn = 2 * d * e + k * 3 * 2 * d * f
    else:
        ffn = 3 * 2 * d * f
    head = 2 * d * m["vocab_size"]
    return m["num_hidden_layers"] * (proj + scores + ffn) + head


def param_count(m: dict) -> int:
    """Parameters the program holds for the configuration: the vocabulary
    padded to a multiple of 256, norms included, the head tied or not."""
    d, hq, hkv, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f = m["intermediate_size"]
    attn = d * hq * hd * 2 + 2 * d * hkv * hd
    if m.get("num_local_experts"):
        e = m["num_local_experts"]
        ffn = e * 3 * d * f + d * e
    else:
        ffn = 3 * d * f
    vocab = -(-m["vocab_size"] // 256) * 256
    emb = vocab * d * (1 if m["tie_word_embeddings"] else 2)
    return m["num_hidden_layers"] * (attn + ffn + 2 * d) + d + emb
