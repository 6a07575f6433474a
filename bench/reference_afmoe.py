"""Plain float32 reference of the AfMoE decoder (Trinity-Mini), and the
seeded weights that both it and the program under test serve.

The harness loads this module by the path a configuration file names and
takes from it `dims`, `n_params`, `program_fields`, `make_weights` and
`score`, as it does `bench/reference.py` (whose float8 rounding, RoPE,
seeding and head statistics this module reuses; nothing here imports the
program).  The weights are made in the parameter layout the program
loads: `dense_layers` (the leading dense-FFN layers) and `layers` (the
MoE layers), each stacked on a leading layer axis; wq and wgate as
[kv_head, d_model, group * d_head], so query head h = k * G + g reads kv
head k = h // G.  Only the experts this chip holds are made: E_held of
the E routed experts, from index `off`.  Arithmetic (transformers'
`AfmoeForCausalLM`, as the configuration file's `assumed` lists it):

  x = embed[tokens] * sqrt(d)
  per layer:  h = rmsnorm(x) * (1 + ln1)
              q, k, v = h Wq, h Wk, h Wv;  q, k = per-head rmsnorm(q, k)
              window layers: rope(q), rope(k)   (global layers: none)
              o = softmax(q k^T / sqrt(d_head), causal [, j > i - W]) v
              x += rmsnorm((o * sigmoid(h Wgate)) Wo) * (1 + ln1_post)
              h = rmsnorm(x) * (1 + ln2)
              dense layers: f = (silu(h Wg) * (h Wu)) Wd
              MoE layers:   s = sigmoid(h Wr)   (float32)
                            top = top-k of s + expert_bias
                            w = route_scale * s[top] / sum(s[top])
                            f = sum over held e in top of w_e SwiGLU_e(h)
                                + SwiGLU_shared(h)
              x += rmsnorm(f) * (1 + ln2_post)
  logits = (rmsnorm(x) * (1 + final_norm)) lm_head^T

Every matmul runs at `Precision.HIGHEST`.  The sequence goes through one
layer at a time, attention and the FFN in blocks of queries, every held
expert computed on every row of a block and weighted by its routed weight
(zero where not routed).  `fp8=True` is the control: every matmul operand
rounded to float8_e4m3, the router's included.

Top-k routing is a comparison, so the model is discontinuous where the
k-th and the next selection scores tie: the bf16 rounding of a served
step moves scores by ~1e-3 and there picks the other expert, whose output
is unrelated.  `score` therefore leaves out the tokens predicted at
positions whose routing is within `ROUTE_TIE` of such a tie in any MoE
layer (`tie_margin`), and judges the rest.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (HI, _layer_params, _mm, _q, _rms, _rope,
                             head_stats, seed_words)


class Dims(NamedTuple):
    d: int          # hidden_size
    H: int          # query heads
    K: int          # kv heads
    dh: int         # head size
    ff: int         # routed and shared experts' width
    ff_dense: int   # the dense layers' FFN width
    V: int          # vocab_size
    Vp: int         # vocab rows held (padded to a multiple of 256)
    L: int          # layers on this chip
    L_pub: int      # layers of the published model
    n_dense: int    # leading dense-FFN layers
    E: int          # routed experts (the router's outputs)
    E_held: int     # experts this chip holds
    off: int        # index of the first held expert
    top_k: int
    n_shared: int
    route_scale: float
    window: int
    global_every: int
    theta: float
    eps: float
    tied: bool = False

    @property
    def G(self) -> int:
        return self.H // self.K

    def is_global(self, layer: int) -> bool:
        return (layer + 1) % self.global_every == 0

    @property
    def attn_params(self) -> int:
        """q, k, v, o and the output gate's matmul parameters."""
        return (2 * self.d * self.H * self.dh + 2 * self.d * self.K * self.dh
                + self.H * self.dh * self.d)

    @property
    def moe_layer_matmul_params(self) -> int:
        """Matmul parameters one token passes through in an MoE layer on
        this chip: attention, router, shared experts and its share
        (top_k x E_held / E) of the routed experts."""
        expert = 3 * self.d * self.ff
        return (self.attn_params + self.d * self.E
                + self.n_shared * expert
                + self.top_k * self.E_held * expert // self.E)

    @property
    def layer_matmul_params(self) -> int:
        """The same, averaged over this chip's layers (dense and MoE)."""
        dense = self.attn_params + 3 * self.d * self.ff_dense
        moe = self.moe_layer_matmul_params
        return (self.n_dense * dense + (self.L - self.n_dense) * moe) \
            // self.L


def dims(conf: Dict) -> Dims:
    """Sizes from a configuration file (Hugging Face key names; the
    published counts under `published`)."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    V = conf["vocab_size"]
    pub = conf.get("published", {})
    return Dims(d=d, H=H, K=conf["num_key_value_heads"],
                dh=conf.get("head_dim", d // H),
                ff=conf["moe_intermediate_size"],
                ff_dense=conf["intermediate_size"], V=V,
                Vp=-(-V // 256) * 256, L=conf["num_hidden_layers"],
                L_pub=pub.get("num_hidden_layers", conf["num_hidden_layers"]),
                n_dense=conf["num_dense_layers"],
                E=pub.get("num_experts", conf["num_experts"]),
                E_held=conf["num_experts"],
                off=conf.get("expert_offset", 0),
                top_k=conf["num_experts_per_tok"],
                n_shared=conf["num_shared_experts"],
                route_scale=float(conf["route_scale"]),
                window=conf["sliding_window"],
                global_every=conf["global_attn_every_n_layers"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                tied=bool(conf["tie_word_embeddings"]))


def n_params(dm: Dims) -> int:
    """Parameters of the published model (every layer and routed
    expert; true vocab)."""
    expert = 3 * dm.d * dm.ff
    norms = 4 * dm.d + 2 * dm.dh
    dense = dm.attn_params + 3 * dm.d * dm.ff_dense + norms
    moe = (dm.attn_params + dm.d * dm.E + dm.E + (dm.E + dm.n_shared) * expert
           + norms)
    return (dm.n_dense * dense + (dm.L_pub - dm.n_dense) * moe
            + dm.V * dm.d * (1 if dm.tied else 2) + dm.d)


def program_fields(dm: Dims) -> Dict:
    """What the program's model config has to say for this reference to
    be the plain version of it (the harness compares field by field)."""
    return {"d_model": dm.d, "n_heads": dm.H, "n_kv_heads": dm.K,
            "d_head": dm.dh, "d_ff": dm.ff, "dense_d_ff": dm.ff_dense,
            "vocab_size": dm.V, "n_layers": dm.L,
            "n_dense_layers": dm.n_dense, "n_experts": dm.E,
            "n_experts_held": dm.E_held, "expert_offset": dm.off,
            "top_k": dm.top_k, "n_shared_experts": dm.n_shared,
            "router": "sigmoid", "route_scale": dm.route_scale,
            "expert_bias": True,
            "tie_embeddings": dm.tied, "rope_theta": dm.theta,
            "rope_global": False, "norm_eps": dm.eps, "attn_bias": False,
            "qk_norm": True, "attn_gate": True, "sandwich_norm": True,
            "embed_scale": dm.d ** 0.5, "gated_mlp": True, "family": "moe",
            "window": dm.window, "global_every": dm.global_every}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

BIAS_SCALE = 0.02   # expert_bias: about the spacing of the top scores
# `score` leaves out tokens routed closer than this to a tie: ~3x the
# median change of a selection score when every matmul operand and the KV
# are rounded to bf16 (the served precision) at Trinity-Mini's widths
ROUTE_TIE = 3e-3


def _leaves(dm: Dims):
    """(path, shape, scale) of every weight, in a fixed order.  Normal
    draws times `scale`: fan-in scaled matrices, small random norm
    offsets, a selection bias of the order of the gaps between the
    largest router scores (so that it changes which experts are chosen
    but is no more than a nudge)."""
    d, K, G, dh, f = dm.d, dm.K, dm.G, dm.dh, dm.ff
    nd, nm = dm.n_dense, dm.L - dm.n_dense
    out = [(("embedding",), (dm.Vp, d), d ** -0.5),
           (("final_norm",), (d,), 0.1)]
    if not dm.tied:
        out.append((("lm_head",), (dm.Vp, d), d ** -0.5))

    def block(stack, n):
        return [((stack, "ln1"), (n, d), 0.1),
                ((stack, "ln2"), (n, d), 0.1),
                ((stack, "ln1_post"), (n, d), 0.1),
                ((stack, "ln2_post"), (n, d), 0.1),
                ((stack, "attn", "wq_w"), (n, K, d, G * dh), d ** -0.5),
                ((stack, "attn", "wk_w"), (n, K, d, dh), d ** -0.5),
                ((stack, "attn", "wv_w"), (n, K, d, dh), d ** -0.5),
                ((stack, "attn", "wo_w"), (n, K * G * dh, d),
                 (K * G * dh) ** -0.5),
                ((stack, "attn", "q_norm"), (n, dh), 0.1),
                ((stack, "attn", "k_norm"), (n, dh), 0.1),
                ((stack, "attn", "wgate_w"), (n, K, d, G * dh), d ** -0.5)]

    fs = dm.n_shared * f
    out += block("dense_layers", nd)
    out += [(("dense_layers", "mlp", "gate_w"), (nd, d, dm.ff_dense),
             d ** -0.5),
            (("dense_layers", "mlp", "up_w"), (nd, d, dm.ff_dense), d ** -0.5),
            (("dense_layers", "mlp", "down_w"), (nd, dm.ff_dense, d),
             dm.ff_dense ** -0.5)]
    out += block("layers", nm)
    out += [(("layers", "moe", "router_w"), (nm, d, dm.E), d ** -0.5),
            (("layers", "moe", "expert_bias"), (nm, dm.E), BIAS_SCALE),
            (("layers", "moe", "w_gate"), (nm, dm.E_held, d, f), d ** -0.5),
            (("layers", "moe", "w_up"), (nm, dm.E_held, d, f), d ** -0.5),
            (("layers", "moe", "w_down"), (nm, dm.E_held, f, d), f ** -0.5),
            (("layers", "moe", "shared", "gate_w"), (nm, d, fs), d ** -0.5),
            (("layers", "moe", "shared", "up_w"), (nm, d, fs), d ** -0.5),
            (("layers", "moe", "shared", "down_w"), (nm, fs, d), fs ** -0.5)]
    return out


@functools.partial(jax.jit, static_argnames=("dm",))
def _make(lo, hi, *, dm: Dims):
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    tree: Dict = {}
    for i, (path, shape, scale) in enumerate(_leaves(dm)):
        val = jax.random.normal(jax.random.fold_in(base, i), shape,
                                jnp.float32) * scale
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = val
    return tree


def make_weights(dm: Dims, seed: int):
    """Float32 weights from `seed` (held experts only), made on the
    default device in one jitted call."""
    lo, hi = seed_words(seed)
    return _make(jnp.uint32(lo), jnp.uint32(hi), dm=dm)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _proj(h, w, fp8):
    """h [S, d] @ w [K, d, f] -> [S, K, f] at HIGHEST."""
    return jnp.einsum("sd,kdf->skf", _q(h, -1, fp8), _q(w, -2, fp8),
                      precision=HI)


def _head_norm(x, scale, eps):
    """RMSNorm over each head's d_head."""
    return _rms(x, scale, eps)


def _swiglu(h, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


def route(h, m, dm: Dims, fp8: bool = False):
    """Routing of rows h [S, d] over all E experts: (chosen experts
    [S, top_k], their weights [S, top_k])."""
    s = jax.nn.sigmoid(_mm(h, m["router_w"], fp8))
    _, top = jax.lax.top_k(s + m["expert_bias"], dm.top_k)
    w = jnp.take_along_axis(s, top, axis=-1)
    return top, dm.route_scale * w / jnp.sum(w, -1, keepdims=True)


def tie_margin(h, m, dm: Dims):
    """Per row of h [S, d], how near the float32 routing is to choosing
    other experts here: the top_k-th selection score less the next one,
    where either of those two experts is held (inf where neither is:
    swapping them changes nothing on this chip)."""
    s = jax.nn.sigmoid(_mm(h, m["router_w"], False))
    vals, top = jax.lax.top_k(s + m["expert_bias"], dm.top_k + 1)
    edge = top[:, -2:]
    held = jnp.any((edge >= dm.off) & (edge < dm.off + dm.E_held), -1)
    return jnp.where(held, vals[:, -2] - vals[:, -1], jnp.inf)


def moe_ffn(h, m, dm: Dims, fp8: bool = False, shared: bool = True):
    """The MoE FFN of rows h [S, d]: the held experts' weighted outputs,
    plus the shared experts' where `shared`."""
    top, w = route(h, m, dm, fp8)
    held = jnp.arange(dm.E_held) + dm.off                      # [Eh]
    cw = jnp.sum(jnp.where(top[:, :, None] == held, w[:, :, None], 0.0),
                 axis=1)                                        # [S, Eh]
    g = jnp.einsum("sd,edf->sef", _q(h, -1, fp8), _q(m["w_gate"], -2, fp8),
                   precision=HI)
    u = jnp.einsum("sd,edf->sef", _q(h, -1, fp8), _q(m["w_up"], -2, fp8),
                   precision=HI)
    y = jnp.einsum("sef,efd->sed", _q(jax.nn.silu(g) * u, -1, fp8),
                   _q(m["w_down"], -2, fp8), precision=HI)      # [S, Eh, d]
    out = jnp.einsum("sed,se->sd", y, cw, precision=HI)
    if shared:
        sh = m["shared"]
        out = out + _swiglu(h, sh["gate_w"], sh["up_w"], sh["down_w"], fp8)
    return out


@functools.partial(jax.jit, static_argnames=("dm", "block", "fp8", "moe",
                                             "is_global"))
def _layer(layers, li, x, *, dm: Dims, block: int, fp8: bool, moe: bool,
           is_global: bool):
    """One decoder layer over the whole (padded) sequence x [S, d]."""
    lp = _layer_params(layers, li)
    a = lp["attn"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"], dm.eps)
    k = _head_norm(_proj(h, a["wk_w"], fp8), a["k_norm"], dm.eps)
    v = _q(_proj(h, a["wv_w"], fp8), -1, fp8)                  # [S, K, dh]
    if not is_global:
        k = _rope(k, pos, dm.theta)
    k = _q(k, -1, fp8)
    nb = S // block

    def attend(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * block, block)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * block, block)
        q = _proj(hb, a["wq_w"], fp8).reshape(block, dm.H, dm.dh)
        q = _head_norm(q, a["q_norm"], dm.eps)
        if not is_global:
            q = _rope(q, pb, dm.theta)
        q = _q(q, -1, fp8).reshape(block, dm.K, dm.G, dm.dh)
        s = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HI) / np.sqrt(dm.dh)
        see = pos[None, None, None, :] <= pb[None, None, :, None]
        if not is_global:
            see &= pos[None, None, None, :] > pb[None, None, :, None] \
                - dm.window
        p = _q(jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1), -1, fp8)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        gate = jax.nn.sigmoid(_proj(hb, a["wgate_w"], fp8))     # [b, K, G*dh]
        o = o.reshape(block, dm.H * dm.dh) * gate.reshape(block, -1)
        return _mm(o, a["wo_w"], fp8)

    aout = jax.lax.map(attend, jnp.arange(nb)).reshape(S, dm.d)
    x = x + _rms(aout, lp["ln1_post"], dm.eps)

    def ffn(i):
        hb = _rms(jax.lax.dynamic_slice_in_dim(x, i * block, block),
                  lp["ln2"], dm.eps)
        if moe:
            return (moe_ffn(hb, lp["moe"], dm, fp8),
                    tie_margin(hb, lp["moe"], dm))
        m = lp["mlp"]
        return (_swiglu(hb, m["gate_w"], m["up_w"], m["down_w"], fp8),
                jnp.full((block,), jnp.inf))

    f, margin = jax.lax.map(ffn, jnp.arange(nb))
    return x + _rms(f.reshape(S, dm.d), lp["ln2_post"], dm.eps), \
        margin.reshape(S)


@functools.partial(jax.jit, static_argnames=("dm",))
def _embed(table, tokens, *, dm: Dims):
    return jnp.take(table, tokens, axis=0) * np.float32(dm.d ** 0.5)


def _stack_of(dm: Dims, li: int):
    """(param stack, index in it, MoE?) of layer li."""
    if li < dm.n_dense:
        return "dense_layers", li, False
    return "layers", li - dm.n_dense, True


def hidden(params, dm: Dims, tokens: np.ndarray, block: int,
           fp8: bool = False, margins: bool = False):
    """Final-layer states [S, d] of `tokens` (padded to a multiple of
    `block`; by causality the padding never reaches a real position);
    with `margins`, also each position's smallest `tie_margin` over the
    MoE layers [S]."""
    x = _embed(params["embedding"], jnp.asarray(tokens, jnp.int32), dm=dm)
    low = jnp.full(x.shape[:1], jnp.inf)
    for li in range(dm.L):
        stack, i, moe = _stack_of(dm, li)
        x, m = _layer(params[stack], jnp.int32(i), x, dm=dm, block=block,
                      fp8=fp8, moe=moe, is_global=dm.is_global(li))
        low = jnp.minimum(low, m)
    return (x, low) if margins else x


def score(params, dm: Dims, prompt, tokens, logprobs, *, pad_to: int,
          block: int, rows: int = 512,
          control: bool = False) -> Dict[str, np.ndarray]:
    """Teacher-forced check of one served request (as
    `bench/reference.py`'s `score`): per served token, `gap` (the
    reference's max logit minus the served token's) and `logprob_err`;
    with `control`, `ctrl_gap` and `ctrl_logprob_err` of the token the
    fp8 control puts first.  A token predicted at a position whose
    routing is a near-tie in some MoE layer (`tie_margin` below
    `ROUTE_TIE`) reads 0 in all four: there any precision below float32
    may pick the other expert, and the served logit shows nothing either
    way.  `tied_share`: the share of served tokens left out so."""
    seq = list(prompt) + list(tokens)
    buf = np.zeros(pad_to, np.int32)
    buf[:len(seq)] = seq
    idx = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    tok = np.asarray(tokens, np.int32)
    x, margin = hidden(params, dm, buf, block, margins=True)
    tied = np.asarray(margin)[idx] < ROUTE_TIE
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
    out = {k: np.where(tied, 0.0, v) for k, v in out.items()}
    out["tied_share"] = np.array([tied.mean()])
    return out


def oneshot_logits(params, dm: Dims, tokens) -> np.ndarray:
    """The same model written in one piece, with no blocks and no layer
    loop: logits [S, V] of `tokens` (for checking `hidden` at small
    sizes)."""
    t = jnp.asarray(tokens, jnp.int32)
    S = t.shape[0]
    pos = jnp.arange(S)
    x = params["embedding"][t] * dm.d ** 0.5
    causal = pos[None, :] <= pos[:, None]
    for li in range(dm.L):
        stack, i, moe = _stack_of(dm, li)
        lp = jax.tree.map(lambda a, i=i: a[i], params[stack])
        a = lp["attn"]
        h = _rms(x, lp["ln1"], dm.eps)
        q = _rms(jnp.einsum("sd,kdf->skf", h, a["wq_w"], precision=HI
                            ).reshape(S, dm.H, dm.dh), a["q_norm"], dm.eps)
        k = _rms(jnp.einsum("sd,kdf->skf", h, a["wk_w"], precision=HI),
                 a["k_norm"], dm.eps)
        v = jnp.einsum("sd,kdf->skf", h, a["wv_w"], precision=HI)
        mask = causal
        if not dm.is_global(li):
            q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
            mask = causal & (pos[None, :] > pos[:, None] - dm.window)
        kr = jnp.repeat(k, dm.G, axis=1)            # head h reads h // G
        vr = jnp.repeat(v, dm.G, axis=1)
        s = jnp.einsum("qhd,shd->hqs", q, kr, precision=HI) / np.sqrt(dm.dh)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqs,shd->qhd", p, vr, precision=HI).reshape(S, -1)
        gate = jax.nn.sigmoid(jnp.einsum("sd,kdf->skf", h, a["wgate_w"],
                                         precision=HI).reshape(S, -1))
        x = x + _rms(jnp.einsum("sf,fd->sd", o * gate, a["wo_w"],
                                precision=HI), lp["ln1_post"], dm.eps)
        h = _rms(x, lp["ln2"], dm.eps)
        if moe:
            f = moe_ffn(h, lp["moe"], dm)
        else:
            m = lp["mlp"]
            f = _swiglu(h, m["gate_w"], m["up_w"], m["down_w"], False)
        x = x + _rms(f, lp["ln2_post"], dm.eps)
    h = _rms(x, params["final_norm"], dm.eps)
    table = params.get("lm_head", params["embedding"])[:dm.V]
    return np.asarray(jnp.einsum("sd,vd->sv", h, table, precision=HI))
