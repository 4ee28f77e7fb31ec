"""The decoder-only transformer with a routed-expert FFN in every layer
(Qwen3-MoE, GraniteMoE): the program's configuration, the weights drawn
from a seed, and the plain reference.

The reference follows the published layer equations in float32 at
``highest`` matmul precision and imports nothing of the program:

    h   = x + Wo · attn(rope(norm_q(Wq · rms(x))), rope(norm_k(Wk · rms(x))), Wv · rms(x))
    out = h + sum_{e in route(h)} g_e · W2_e (silu(W1_e · rms(h)) * (W3_e · rms(h)))
    logits = head(rms(out_L))

Routing is softmax top-k over the router logits, renormalised over the
chosen k. In decode, where the configuration states an XShare policy,
the batch first selects an expert set S (Algorithm 2 of the XShare
paper: each live token's top-k0 experts, plus the m_l experts with the
largest summed gate probability over the live tokens) and every token
takes its top-k within S. Prefill routes without a policy.

Departures from the published models, all stated in the configuration
file's ``reduced``: no muP scalars (Granite), RMSNorm epsilon 1e-5, the
vocabulary padded to a multiple of 256 with the padded logits at -1e30.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a scale per row of the contraction (what an fp8 serving
path would compute), products accumulated in float32.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
VOCAB_PAD = 256
NEG = -1e30


def dims(cfg: Dict) -> Dict:
    """Sizes of the configuration under the names this module uses."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    e = cfg.get("num_experts", cfg.get("num_local_experts"))
    f = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    v = cfg["vocab_size"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                Hkv=cfg["num_key_value_heads"],
                dh=cfg.get("head_dim") or d // h, E=e,
                k=cfg["num_experts_per_tok"], f=f, V=v,
                Vp=-(-v // VOCAB_PAD) * VOCAB_PAD,
                tied=bool(cfg["tie_word_embeddings"]),
                qk_norm=bool(cfg.get("qk_norm", False)),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def program_config(cfg: Dict):
    """The program's ArchConfig for this configuration."""
    from repro.configs.base import ArchConfig, AttnConfig, MoEConfig
    z = dims(cfg)
    return ArchConfig(
        name=cfg["name"], family="moe", num_layers=z["L"], d_model=z["d"],
        d_ff=0, vocab_size=z["V"],
        attn=AttnConfig(num_heads=z["H"], num_kv_heads=z["Hkv"],
                        head_dim=z["dh"], rope_theta=z["theta"],
                        qk_norm=z["qk_norm"]),
        moe=MoEConfig(num_experts=z["E"], top_k=z["k"], d_ff_expert=z["f"],
                      normalize_gates=bool(cfg.get("norm_topk_prob", True))),
        moe_every=1, tie_embeddings=z["tied"], norm_eps=z["eps"],
        vocab_pad_to=VOCAB_PAD, citation=cfg["source"])


def weight_shapes(cfg: Dict) -> Dict:
    """{path: (shape, dtype name, kind)} of every weight, in the layout
    the program reads. kind: "normal" (std 0.02) or "ones"."""
    z = dims(cfg)
    L, d, E, f = z["L"], z["d"], z["E"], z["f"]
    qd, kvd = z["H"] * z["dh"], z["Hkv"] * z["dh"]
    w = {
        "embed": ((z["Vp"], d), "bfloat16", "normal"),
        "layers/attn_norm": ((L, d), "bfloat16", "ones"),
        "layers/attn/wq": ((L, d, qd), "bfloat16", "normal"),
        "layers/attn/wk": ((L, d, kvd), "bfloat16", "normal"),
        "layers/attn/wv": ((L, d, kvd), "bfloat16", "normal"),
        "layers/attn/wo": ((L, qd, d), "bfloat16", "normal"),
        "layers/moe_norm": ((L, d), "bfloat16", "ones"),
        # the program keeps its router in float32; it holds bf16 values
        "layers/moe/wg": ((L, d, E), "float32", "normal"),
        "layers/moe/w1": ((L, E, d, f), "bfloat16", "normal"),
        "layers/moe/w3": ((L, E, d, f), "bfloat16", "normal"),
        "layers/moe/w2": ((L, E, f, d), "bfloat16", "normal"),
        "final_norm": ((d,), "bfloat16", "ones"),
    }
    if z["qk_norm"]:
        w["layers/attn/q_norm"] = ((L, z["dh"]), "bfloat16", "ones")
        w["layers/attn/k_norm"] = ((L, z["dh"]), "bfloat16", "ones")
    if not z["tied"]:
        w["lm_head"] = ((d, z["Vp"]), "bfloat16", "normal")
    return w


def _nest(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two 31-bit words (the draw's arguments, so
    that one compiled program serves every seed)."""
    s = int(seed) % (1 << 62)
    return np.array([s & 0x7FFFFFFF, s >> 31], np.int32)


def leaf_key(words, index: int):
    """A key per leaf from the seed's two words."""
    key = jax.random.PRNGKey(words[0])
    key = jax.random.fold_in(key, words[1])
    return jax.random.fold_in(key, index)


CHUNK = 1 << 22   # elements drawn per step: bounds the draw's scratch


def _leaf(words, index, shape, dtype, kind):
    """One weight: N(0, 0.02^2) in bfloat16 (or ones), drawn CHUNK
    elements at a time from its own key, so no float32 or random-bits
    temporary of the whole leaf is ever held."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    key = leaf_key(words, index)
    n = int(np.prod(shape))
    steps = -(-n // CHUNK)
    while n % steps:          # equal blocks: the stack is the leaf itself
        steps += 1

    def draw(i):
        x = jax.random.normal(jax.random.fold_in(key, i), (n // steps,),
                              jnp.bfloat16)
        return x * jnp.bfloat16(STD)

    return jax.lax.map(draw, jnp.arange(steps)).reshape(shape).astype(dtype)


def make_weights(cfg: Dict, seed: int):
    """Every weight drawn from `seed` in one jitted call on the device,
    in the nested layout the program reads."""
    shapes = weight_shapes(cfg)

    def build(words):
        return _nest({path: _leaf(words, i, shape, jnp.dtype(dt), kind)
                      for i, (path, (shape, dt, kind))
                      in enumerate(sorted(shapes.items()))})

    return jax.jit(build)(seed_words(seed))


# ------------------------------------------------------------ reference --

def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, precision):
    """a (..., K) @ b (K, N) in float32 (control: fp8 operands)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if precision == "fp8":
        return jnp.matmul(_q8(a, -1), _q8(b, 0),
                          precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    x = jnp.asarray(x, jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * jnp.asarray(w, jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (..., T, heads, dh), pos (..., T)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _qkv(z, lw, x, pos, precision):
    h = _rms(x, lw["attn_norm"], z["eps"])
    sh = x.shape[:-1]
    q = _mm(h, lw["attn"]["wq"], precision).reshape(sh + (z["H"], z["dh"]))
    k = _mm(h, lw["attn"]["wk"], precision).reshape(sh + (z["Hkv"], z["dh"]))
    v = _mm(h, lw["attn"]["wv"], precision).reshape(sh + (z["Hkv"], z["dh"]))
    if z["qk_norm"]:
        q = _rms(q, lw["attn"]["q_norm"], z["eps"])
        k = _rms(k, lw["attn"]["k_norm"], z["eps"])
    return _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"]), v


def _attend(z, q, k, v, mask, precision):
    """q (B,T,H,dh), k/v (B,S,Hkv,dh), mask (B,T,S) -> (B,T,H*dh).
    Query heads share their group's key and value head."""
    B, T = q.shape[:2]
    rep = z["H"] // z["Hkv"]
    if precision == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 1)
    hp = jax.lax.Precision.HIGHEST
    qg = q.reshape(B, T, z["Hkv"], rep, z["dh"])
    s = jnp.einsum("btgrd,bsgd->bgrts", qg, k, precision=hp) \
        / math.sqrt(z["dh"])
    s = jnp.where(mask[:, None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrts,bsgd->btgrd", p, v, precision=hp)
    return o.reshape(B, T, -1)


def _route(z, logits, live, xshare):
    """Gate matrix (T, E): top-k within the batch's XShare set (decode)
    or within all experts (prefill, xshare None). live: (T,) bool."""
    probs = jax.nn.softmax(logits, -1) * live[:, None]
    E = z["E"]
    if xshare is None:
        allowed = jnp.ones((E,), bool)
    else:
        k0, m = xshare["k0"], xshare["m_l"]
        top0 = jax.lax.top_k(probs, k0)[1]
        warm = (jax.nn.one_hot(top0, E, dtype=bool).any(1)
                & live[:, None]).any(0)
        pool = jnp.where(warm, -jnp.inf, probs.sum(0))
        extra = jax.nn.one_hot(jax.lax.top_k(pool, m)[1], E,
                               dtype=bool).any(0)
        allowed = warm | extra
    masked = jnp.where(allowed[None], logits, -jnp.inf)
    top_l, idx = jax.lax.top_k(masked, z["k"])
    g = jax.nn.softmax(top_l, -1)
    g = jnp.where(jnp.isfinite(top_l), g, 0.0) * live[:, None]
    return (jax.nn.one_hot(idx, E) * g[..., None]).sum(1)


def _experts(z, experts, l, x, gate, precision):
    """sum_e gate[:, e] * FFN_e(x) over layer l's experts, one at a time.
    experts: the stacked (L, E, ...) w1, w3, w2; x (T, d)."""
    w1s, w3s, w2s = experts

    def one(acc, e):
        w1, w3, w2 = (jax.lax.dynamic_slice_in_dim(
            jax.lax.dynamic_index_in_dim(w, l, 0, False), e, 1, 0)[0]
            for w in (w1s, w3s, w2s))
        h = jax.nn.silu(_mm(x, w1, precision)) * _mm(x, w3, precision)
        g = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)
        return acc + g * _mm(h, w2, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(z["E"]))
    return y


def _moe(z, lw, experts, l, x, live, xshare, precision):
    h = _rms(x, lw["moe_norm"], z["eps"])
    logits = _mm(h, lw["moe"]["wg"], precision)
    gate = _route(z, logits, live, xshare)
    return x + _experts(z, experts, l, h, gate, precision)


def _head(z, w, x, precision):
    h = _rms(x, w["final_norm"], z["eps"])
    table = w["embed"].T if z["tied"] else w["lm_head"]
    return _mm(h, table, precision)[..., :z["V"]]


def _split(w):
    """(layer leaves without the experts, the stacked experts)."""
    small = {k: v for k, v in w["layers"].items() if k != "moe"}
    small["moe"] = {"wg": w["layers"]["moe"]["wg"]}
    m = w["layers"]["moe"]
    return small, (m["w1"], m["w3"], m["w2"])


class Reference:
    """The plain model, walked the way the served batch was: prompts
    through a full causal pass, then one token per live slot per decode
    step against a float32 cache of every earlier position. The weights
    are drawn again from the seed."""

    def __init__(self, cfg: Dict, seed: int, num_slots: int, cache_len: int,
                 precision: str = "f32"):
        self.z = dims(cfg)
        self.precision = precision
        self.xshare = cfg.get("xshare")
        if self.xshare and self.xshare["mode"] != "batch":
            raise NotImplementedError(f"xshare mode {self.xshare['mode']}")
        self.w = make_weights(cfg, seed)
        z = self.z
        shape = (z["L"], num_slots, cache_len, z["Hkv"], z["dh"])
        self.k = jnp.zeros(shape, jnp.float32)
        self.v = jnp.zeros(shape, jnp.float32)
        self.pos = np.zeros(num_slots, np.int64)
        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1, 2))

    def _prefill_fn(self, w, tokens):
        """One prompt (1, T): last-position logits, keys and values."""
        z, P = self.z, self.precision
        small, experts = _split(w)
        T = tokens.shape[1]
        x = jnp.take(jnp.asarray(w["embed"], jnp.float32), tokens, axis=0)
        pos = jnp.arange(T)[None]
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        live = jnp.ones((T,), bool)

        def layer(x, xs):
            lw, l = xs
            q, k, v = _qkv(z, lw, x, pos, P)
            x = x + _mm(_attend(z, q, k, v, causal, P), lw["attn"]["wo"], P)
            x = _moe(z, lw, experts, l, x[0], live, None, P)[None]
            return x, (k[0], v[0])

        x, (ks, vs) = jax.lax.scan(layer, x, (small, jnp.arange(z["L"])))
        return _head(z, w, x[0, -1], P), ks, vs

    def _decode_fn(self, w, kc, vc, tok, pos, live):
        z, P = self.z, self.precision
        small, experts = _split(w)
        S = kc.shape[2]
        x = jnp.take(jnp.asarray(w["embed"], jnp.float32), tok, axis=0)
        x = x[:, None]                                      # (B,1,d)
        mask = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, :]
        hit = (jnp.arange(S)[None, :] == pos[:, None])[:, :, None, None]

        def layer(carry, xs):
            x, kc, vc = carry
            lw, l = xs
            q, k, v = _qkv(z, lw, x, pos[:, None], P)
            kl = jnp.where(hit, k, jax.lax.dynamic_index_in_dim(kc, l, 0,
                                                                False))
            vl = jnp.where(hit, v, jax.lax.dynamic_index_in_dim(vc, l, 0,
                                                                False))
            kc = jax.lax.dynamic_update_index_in_dim(kc, kl, l, 0)
            vc = jax.lax.dynamic_update_index_in_dim(vc, vl, l, 0)
            x = x + _mm(_attend(z, q, kl, vl, mask, P), lw["attn"]["wo"], P)
            x = _moe(z, lw, experts, l, x[:, 0], live, self.xshare,
                     P)[:, None]
            return (x, kc, vc), None

        (x, kc, vc), _ = jax.lax.scan(layer, (x, kc, vc),
                                      (small, jnp.arange(z["L"])))
        return _head(z, w, x[:, 0], P), kc, vc

    def close(self):
        """Frees the reference's weights and cache on the device."""
        for a in jax.tree_util.tree_leaves((self.w, self.k, self.v)):
            a.delete()
        self.w = self.k = self.v = None
        self._prefill = self._decode = None

    def prefill(self, prompt: np.ndarray):
        """One prompt (T,): logits (V,) at its last position, and its
        float32 keys and values (L, T, Hkv, dh)."""
        lg, k, v = self._prefill(self.w, jnp.asarray(prompt)[None])
        return np.asarray(lg), (k, v)

    def insert(self, slot: int, kv):
        k, v = kv
        n = k.shape[1]
        self.k = self.k.at[:, slot, :n].set(k)
        self.v = self.v.at[:, slot, :n].set(v)
        self.pos[slot] = n

    def decode(self, tok: np.ndarray, live: np.ndarray) -> np.ndarray:
        """One step of every slot: tok (B,) the token each slot feeds,
        live (B,) which slots take part. Returns logits (B, V); the
        positions of live slots advance by one."""
        lg, self.k, self.v = self._decode(
            self.w, self.k, self.v, jnp.asarray(tok, jnp.int32),
            jnp.asarray(self.pos, jnp.int32), jnp.asarray(live))
        self.pos = self.pos + live.astype(np.int64)
        return np.asarray(lg)
