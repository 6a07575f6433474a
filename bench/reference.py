"""Plain float32 reference of the dense GQA decoder (Qwen1.5 / Qwen2.5),
and the seeded weights that both it and the program under test serve.

A configuration file names its reference module (`"reference"`); the
harness loads it by that path and takes from it `dims`, `n_params`,
`program_fields`, `make_weights` and `score`, so another architecture
brings a module of its own beside this one.  Nothing here imports the
program.  The weights are made from the seed by
`make_weights`, in the parameter layout the program loads (wq as
[kv_head, d_model, group * d_head], so query head h = k * G + g reads kv
head k = h // G); the reference reads the same layout.  Arithmetic:

  x = embed[tokens]
  per layer:  h = rmsnorm(x) * (1 + ln1)
              q, k, v = h Wq + bq, h Wk + bk, h Wv + bv; rope(q), rope(k)
              x += softmax(q k^T / sqrt(d_head), causal) v Wo
              h = rmsnorm(x) * (1 + ln2)
              x += (silu(h Wg) * (h Wu)) Wd
  logits = (rmsnorm(x) * (1 + final_norm)) head^T     (head = embed if tied)

Every matmul runs at `Precision.HIGHEST` (true float32 on a TPU).  The
sequence goes through one layer at a time, and attention runs in blocks
of queries against the whole key range, so a 16K-token context fits in a
few GB after the program has been freed.  `fp8=True` is the control: the
same arithmetic with every matmul operand rounded to float8_e4m3 (scaled
by its absmax along the contracted axis), the precision step below the
bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


class Dims(NamedTuple):
    d: int          # hidden_size
    H: int          # query heads
    K: int          # kv heads
    dh: int         # head size
    ff: int         # intermediate_size
    V: int          # vocab_size
    Vp: int         # vocab rows held (padded to a multiple of 256)
    L: int          # layers
    tied: bool
    theta: float
    eps: float

    @property
    def G(self) -> int:
        return self.H // self.K

    @property
    def layer_matmul_params(self) -> int:
        """Matmul parameters one token passes through in one layer."""
        return (self.d * self.H * self.dh + 2 * self.d * self.K * self.dh
                + self.H * self.dh * self.d + 3 * self.d * self.ff)


def dims(conf: Dict) -> Dims:
    """Sizes from a configuration file (Hugging Face key names)."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    V = conf["vocab_size"]
    return Dims(d=d, H=H, K=conf["num_key_value_heads"],
                dh=conf.get("head_dim", d // H),
                ff=conf["intermediate_size"], V=V, Vp=-(-V // 256) * 256,
                L=conf["num_hidden_layers"],
                tied=bool(conf["tie_word_embeddings"]),
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]))


def n_params(dm: Dims) -> int:
    """Parameters of the published model at these sizes (true vocab)."""
    per_layer = (dm.layer_matmul_params                    # q, k, v, o, mlp
                 + dm.H * dm.dh + 2 * dm.K * dm.dh         # qkv biases
                 + 2 * dm.d)                               # norms
    embed = dm.V * dm.d * (1 if dm.tied else 2)
    return dm.L * per_layer + embed + dm.d


def program_fields(dm: Dims) -> Dict:
    """What the program's model config has to say for this reference to
    be the plain version of it (the harness compares field by field)."""
    return {"d_model": dm.d, "n_heads": dm.H, "n_kv_heads": dm.K,
            "d_head": dm.dh, "d_ff": dm.ff, "vocab_size": dm.V,
            "n_layers": dm.L, "tie_embeddings": dm.tied,
            "rope_theta": dm.theta, "norm_eps": dm.eps, "attn_bias": True,
            "gated_mlp": True, "family": "dense", "window": None}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _leaves(dm: Dims):
    """(path, shape, scale, stacked) of every weight, in a fixed order.
    Normal draws times `scale`: fan-in scaled matrices, small random
    biases and norm offsets (so that every parameter matters)."""
    d, K, G, dh, ff, L = dm.d, dm.K, dm.G, dm.dh, dm.ff, dm.L
    out = [(("embedding",), (dm.Vp, d), d ** -0.5, False),
           (("final_norm",), (d,), 0.1, False)]
    if not dm.tied:
        out.append((("lm_head",), (dm.Vp, d), d ** -0.5, False))
    out += [(("layers", "ln1"), (d,), 0.1, True),
            (("layers", "ln2"), (d,), 0.1, True),
            (("layers", "attn", "wq_w"), (K, d, G * dh), d ** -0.5, True),
            (("layers", "attn", "wk_w"), (K, d, dh), d ** -0.5, True),
            (("layers", "attn", "wv_w"), (K, d, dh), d ** -0.5, True),
            (("layers", "attn", "wq_b"), (K, G * dh), 0.1, True),
            (("layers", "attn", "wk_b"), (K, dh), 0.1, True),
            (("layers", "attn", "wv_b"), (K, dh), 0.1, True),
            (("layers", "attn", "wo_w"), (K * G * dh, d),
             (K * G * dh) ** -0.5, True),
            (("layers", "mlp", "gate_w"), (d, ff), d ** -0.5, True),
            (("layers", "mlp", "up_w"), (d, ff), d ** -0.5, True),
            (("layers", "mlp", "down_w"), (ff, d), ff ** -0.5, True)]
    return [(p, (L,) + s if st else s, sc, st) for p, s, sc, st in out]


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    s = int(seed) % 2**64
    return np.uint32(s & 0xFFFFFFFF), np.uint32(s >> 32)


@functools.partial(jax.jit, static_argnames=("dm",))
def _make(lo, hi, *, dm: Dims):
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    tree: Dict = {}
    for i, (path, shape, scale, _) in enumerate(_leaves(dm)):
        val = jax.random.normal(jax.random.fold_in(base, i), shape,
                                jnp.float32) * scale
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = val
    return tree


def make_weights(dm: Dims, seed: int):
    """Float32 weights from `seed`, made on the default device in one
    jitted call."""
    lo, hi = seed_words(seed)
    return _make(jnp.uint32(lo), jnp.uint32(hi), dm=dm)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fq(x, axis):
    """Round to float8_e4m3, scaled by the absmax along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(x, axis, fp8):
    return _fq(x, axis) if fp8 else x


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """x: [S, n, dh]; rotate-half rope at positions `pos` [S]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(x, w, fp8):
    """x @ w at HIGHEST; fp8: both operands rounded along the contraction."""
    return jnp.einsum("sd,df->sf", _q(x, -1, fp8), _q(w, -2, fp8),
                      precision=HI)


def _layer_params(layers, li):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
        layers)


@functools.partial(jax.jit, static_argnames=("dm", "block", "fp8"))
def _layer(layers, li, x, *, dm: Dims, block: int, fp8: bool):
    """One decoder layer over the whole (padded) sequence x [S, d]."""
    lp = _layer_params(layers, li)
    a = lp["attn"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"], dm.eps)
    k = jnp.einsum("sd,kdf->skf", _q(h, -1, fp8), _q(a["wk_w"], -2, fp8),
                   precision=HI) + a["wk_b"]
    v = jnp.einsum("sd,kdf->skf", _q(h, -1, fp8), _q(a["wv_w"], -2, fp8),
                   precision=HI) + a["wv_b"]
    k = _q(_rope(k, pos, dm.theta), -1, fp8)                   # [S, K, dh]
    v = _q(v, -1, fp8)
    nb = S // block

    def attend(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * block, block)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * block, block)
        q = jnp.einsum("sd,kdf->skf", _q(hb, -1, fp8),
                       _q(a["wq_w"], -2, fp8), precision=HI) + a["wq_b"]
        q = q.reshape(block, dm.K * dm.G, dm.dh)
        q = _q(_rope(q, pb, dm.theta), -1, fp8)
        q = q.reshape(block, dm.K, dm.G, dm.dh)
        s = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HI) / np.sqrt(dm.dh)
        s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                      s, -jnp.inf)
        p = _q(jax.nn.softmax(s, axis=-1), -1, fp8)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        return _mm(o.reshape(block, dm.H * dm.dh), a["wo_w"], fp8)

    x = x + jax.lax.map(attend, jnp.arange(nb)).reshape(S, dm.d)
    m = lp["mlp"]

    def ffn(i):
        hb = _rms(jax.lax.dynamic_slice_in_dim(x, i * block, block),
                  lp["ln2"], dm.eps)
        g = _mm(hb, m["gate_w"], fp8)
        u = _mm(hb, m["up_w"], fp8)
        return _mm(jax.nn.silu(g) * u, m["down_w"], fp8)

    return x + jax.lax.map(ffn, jnp.arange(nb)).reshape(S, dm.d)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("dm", "fp8"))
def _head(params, x, idx, tokens, *, dm: Dims, fp8: bool):
    """Per position idx of x [S, d]: (max logit, its token, logit of
    `tokens`, logsumexp), over the true vocabulary."""
    table = params.get("lm_head", params["embedding"])[:dm.V]
    h = _rms(jnp.take(x, idx, axis=0), params["final_norm"], dm.eps)
    lg = jnp.einsum("nd,vd->nv", _q(h, -1, fp8), _q(table, -1, fp8),
                    precision=HI)
    at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
    return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32), at,
            jax.nn.logsumexp(lg, -1))


def hidden(params, dm: Dims, tokens: np.ndarray, block: int,
           fp8: bool = False):
    """Final-layer states [S, d] of `tokens` (padded to a multiple of
    `block`; by causality the padding never reaches a real position)."""
    x = _embed(params["embedding"], jnp.asarray(tokens, jnp.int32))
    for li in range(dm.L):
        x = _layer(params["layers"], jnp.int32(li), x, dm=dm, block=block,
                   fp8=fp8)
    return x


def head_stats(params, dm: Dims, x, idx, tokens, rows: int,
               fp8: bool = False):
    """`_head` at positions `idx`, in fixed blocks of `rows` (one compiled
    shape); returns host arrays."""
    n = len(idx)
    out = []
    for s in range(0, n, rows):
        ib = np.zeros(rows, np.int32)
        tb = np.zeros(rows, np.int32)
        ib[:len(idx[s:s + rows])] = idx[s:s + rows]
        tb[:len(idx[s:s + rows])] = tokens[s:s + rows]
        out.append(jax.device_get(_head(params, x, jnp.asarray(ib),
                                        jnp.asarray(tb), dm=dm, fp8=fp8)))
    return [np.concatenate([o[i] for o in out])[:n] for i in range(4)]


def score(params, dm: Dims, prompt, tokens, logprobs, *, pad_to: int,
          block: int, rows: int = 512,
          control: bool = False) -> Dict[str, np.ndarray]:
    """Teacher-forced check of one served request: served token j was
    predicted at position len(prompt) - 1 + j.  Per served token: `gap`,
    the reference's max logit minus the served token's, and `logprob_err`,
    |served logprob - reference logprob|.  With `control`, `ctrl_gap`
    and `ctrl_logprob_err`: the same two numbers for the token the fp8
    control puts first at each position, with its own logprob."""
    seq = list(prompt) + list(tokens)
    buf = np.zeros(pad_to, np.int32)
    buf[:len(seq)] = seq
    idx = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    tok = np.asarray(tokens, np.int32)
    x = hidden(params, dm, buf, block)
    mx, _, at, lse = head_stats(params, dm, x, idx, tok, rows)
    out = {"gap": mx - at,
           "logprob_err": np.abs(np.asarray(logprobs) - (at - lse))}
    if control:
        x8 = hidden(params, dm, buf, block, fp8=True)
        mx8, t8, _, lse8 = head_stats(params, dm, x8, idx, tok, rows,
                                      fp8=True)
        del x8
        mx, _, at, lse = head_stats(params, dm, x, idx, t8, rows)
        out["ctrl_gap"] = mx - at
        out["ctrl_logprob_err"] = np.abs((mx8 - lse8) - (at - lse))
    return out


def oneshot_logits(params, dm: Dims, tokens) -> np.ndarray:
    """The same model written in one piece, with no blocks and no layer
    loop: logits [S, V] of `tokens` (for checking `hidden` at small
    sizes)."""
    t = jnp.asarray(tokens, jnp.int32)
    S = t.shape[0]
    pos = jnp.arange(S)
    x = params["embedding"][t]
    mask = pos[None, :] <= pos[:, None]
    for li in range(dm.L):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        a = lp["attn"]
        h = _rms(x, lp["ln1"], dm.eps)
        q = (jnp.einsum("sd,kdf->skf", h, a["wq_w"], precision=HI)
             + a["wq_b"]).reshape(S, dm.H, dm.dh)
        k = jnp.einsum("sd,kdf->skf", h, a["wk_w"], precision=HI) + a["wk_b"]
        v = jnp.einsum("sd,kdf->skf", h, a["wv_w"], precision=HI) + a["wv_b"]
        q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
        kr = jnp.repeat(k, dm.G, axis=1)            # head h reads h // G
        vr = jnp.repeat(v, dm.G, axis=1)
        s = jnp.einsum("qhd,shd->hqs", q, kr, precision=HI) / np.sqrt(dm.dh)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqs,shd->qhd", p, vr, precision=HI)
        x = x + jnp.einsum("sf,fd->sd", o.reshape(S, -1), a["wo_w"],
                           precision=HI)
        h = _rms(x, lp["ln2"], dm.eps)
        m = lp["mlp"]
        g = jnp.einsum("sd,df->sf", h, m["gate_w"], precision=HI)
        u = jnp.einsum("sd,df->sf", h, m["up_w"], precision=HI)
        x = x + jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u, m["down_w"],
                           precision=HI)
    table = params.get("lm_head", params["embedding"])[:dm.V]
    h = _rms(x, params["final_norm"], dm.eps)
    return np.asarray(jnp.einsum("sd,vd->sv", h, table, precision=HI))
