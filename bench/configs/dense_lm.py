"""Plain reference for the dense decoder configurations: straightforward
``jax.numpy`` in float32 at the highest matmul precision, with no kernel,
cache or batching of requests, and nothing taken from the program.

It computes the published model, but for the departures that the
configuration's ``departures`` group states the program makes (here: token
embeddings scaled by sqrt(hidden_size), and RMSNorm's eps); without that
group it computes the published form.  Blocks are pre-norm: attention with rotary embeddings on the
two halves of each head (GQA when there are fewer KV heads), then a SwiGLU
MLP; a final norm, then the output head (the embedding's transpose when
tied).

Weights come from ``bench.weights`` by their path in the parameter tree,
so the reference regenerates the seed's weights itself.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 (per-tensor absmax scaling), the nearest precision
below the configuration's bfloat16 compute.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights

F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _dot8(a, b):
    return _mm(_q8(a), _q8(b))


def _dot8_fwd(a, b):
    a8, b8 = _q8(a), _q8(b)
    return _mm(a8, b8), (a8, b8)


def _dot8_bwd(res, g):
    a8, b8 = res
    g8 = _q8(g)
    da = _mm(g8, b8.T)
    db = _mm(a8.reshape(-1, a8.shape[-1]).T, g8.reshape(-1, g8.shape[-1]))
    return da, db


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def dot(a, b, precision):
    """x (..., K) @ w (K, N).  With ``"fp8"`` both operands of the product,
    and in the backward pass the incoming gradient too, are rounded to
    float8 e4m3 with per-tensor scaling; products accumulate in float32."""
    if precision == "fp8":
        return _dot8(a, b)
    if precision == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def shapes(conf) -> dict:
    """The parameter tree's layout: ``stack/0/...`` leaves carry the layer
    axis first."""
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, L = d // hq, conf["num_hidden_layers"]
    f32 = jnp.float32
    S = lambda *s: jax.ShapeDtypeStruct(s, f32)
    blk = {"attn": {"wq": S(L, d, hq * hd), "wk": S(L, d, hkv * hd),
                    "wv": S(L, d, hkv * hd), "wo": S(L, hq * hd, d)},
           "ffn": {"w1": S(L, d, f), "w3": S(L, d, f), "w2": S(L, f, d)}}
    tree = {"embed": S(V, d), "stack": {"0": blk}}
    if conf["norm"] != "nonparametric":
        blk["ln1"] = {"scale": S(L, d)}
        blk["ln2"] = {"scale": S(L, d)}
        tree["final_norm"] = {"scale": S(d)}
    else:
        blk["ln1"], blk["ln2"], tree["final_norm"] = {}, {}, {}
    if not conf["tie_word_embeddings"]:
        tree["head"] = S(d, V)
    return tree


def embed_scale(conf) -> float:
    dep = conf.get("departures", {})
    return math.sqrt(conf["hidden_size"]) if dep.get("scale_embeddings_by_sqrt_hidden_size") else 1.0


def norm(conf, x, scale=None):
    if conf["norm"] == "nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5)
    eps = conf.get("departures", {}).get("rms_norm_eps", conf["rms_norm_eps"])
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(conf, p, x, precision):
    """One pre-norm block over x: (B, S, d)."""
    b, s, d = x.shape
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // hq
    ln1 = p.get("ln1", {}).get("scale")
    ln2 = p.get("ln2", {}).get("scale")
    h = norm(conf, x, ln1)
    q = dot(h, p["attn"]["wq"], precision).reshape(b, s, hq, hd)
    k = dot(h, p["attn"]["wk"], precision).reshape(b, s, hkv, hd)
    v = dot(h, p["attn"]["wv"], precision).reshape(b, s, hkv, hd)
    q, k = rope(q, conf["rope_theta"]), rope(k, conf["rope_theta"])
    g = hq // hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bthd->bhqt", q, k,
                    precision=lax.Precision.HIGHEST) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", pr, v,
                   precision=lax.Precision.HIGHEST).reshape(b, s, hq * hd)
    x = x + dot(o, p["attn"]["wo"], precision)
    h = norm(conf, x, ln2)
    u = jax.nn.silu(dot(h, p["ffn"]["w1"], precision)) * dot(h, p["ffn"]["w3"], precision)
    return x + dot(u, p["ffn"]["w2"], precision)


def layer_params(params, l):
    return jax.tree.map(lambda a: a[l], params["stack"]["0"])


def unembed(conf, params):
    return params["embed"].T if conf["tie_word_embeddings"] else params["head"]


def hidden(conf, params, tokens, precision="f32"):
    """Final normed hidden states (B, S, d) of a whole-parameter tree."""
    x = params["embed"][tokens] * embed_scale(conf)
    body = jax.checkpoint(lambda x, p: block(conf, p, x, precision))
    for l in range(conf["num_hidden_layers"]):
        x = body(x, layer_params(params, l))
    return norm(conf, x, params.get("final_norm", {}).get("scale"))


# --------------------------------------------------------------------------
# serving: logits gaps of served tokens, one layer's weights at a time
# --------------------------------------------------------------------------

def _layer_weights(conf, words, l):
    tree = shapes(conf)["stack"]["0"]
    return jax.tree_util.tree_map_with_path(
        lambda path, s: weights.leaf_value(words, "stack/0/" + weights._path_str(path),
                                           s.shape[1:], l), tree)


def _conf_key(conf):
    return json.dumps({k: v for k, v in conf.items() if k != "limits"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _serve_programs(key, precision):
    """The reference's programs for one configuration: the seed is an
    argument of each, so every seed runs the same compiled programs.  The
    weights are made by a call of their own: fused into the block's
    matmuls, their hash would be recomputed for every tile."""
    conf = json.loads(key)
    embed = jax.jit(lambda e, t: e[t] * embed_scale(conf))
    layer_w = jax.jit(lambda words, l: _layer_weights(conf, words, l))
    one = jax.jit(lambda x, w: block(conf, w, x, precision))
    final = jax.jit(lambda x, scale: norm(conf, x, scale))
    return embed, layer_w, one, final


def serve_hidden(conf, seed, tokens, precision="f32"):
    """Final hidden states of ``tokens`` (B, S), regenerating each layer's
    weights from the seed so only one layer is held at a time."""
    top = shapes(conf)
    embed, layer_w, one, final = _serve_programs(_conf_key(conf), precision)
    x = embed(weights.leaf(seed, "embed", top["embed"].shape), tokens)
    words = weights.seed_words(seed)
    for l in range(conf["num_hidden_layers"]):
        x = one(x, layer_w(words, l))
    scale = (weights.leaf(seed, "final_norm/scale", top["final_norm"]["scale"].shape)
             if "scale" in top["final_norm"] else None)
    return final(x, scale)


def served_gaps(conf, seed, tokens, targets, valid, control_hidden=None, chunk=128):
    """Per position, how far the served token's reference logit lies below
    the reference's best.  tokens: (B, S) inputs; targets: (B, S) the token
    served at each position; valid: (B, S) bool.  With ``control_hidden``,
    the gap of the token the control puts first instead."""
    h = serve_hidden(conf, seed, tokens, "f32")
    top = shapes(conf)
    w = (weights.leaf(seed, "embed", top["embed"].shape).T
         if conf["tie_word_embeddings"]
         else weights.leaf(seed, "head", top["head"].shape))

    out = []
    for i in range(0, tokens.shape[1], chunk):
        sl = slice(i, i + chunk)
        hc = None if control_hidden is None else control_hidden[:, sl]
        out.append(_gaps(h[:, sl], hc, targets[:, sl], valid[:, sl], w))
    return jnp.concatenate(out, axis=1)


@jax.jit
def _gaps(h, hc, tgt, ok, w):
    lg = jnp.matmul(h, w, precision=lax.Precision.HIGHEST)
    best = jnp.max(lg, -1)
    if hc is not None:
        tgt = jnp.argmax(dot(hc, w, "fp8"), -1)
    got = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
    return jnp.where(ok, best - got, 0.0)


# --------------------------------------------------------------------------
# training: three AdamW steps of the plain model
# --------------------------------------------------------------------------

def loss_sum(conf, params, tokens, labels, precision):
    h = hidden(conf, params, tokens, precision)
    lg = dot(h, unembed(conf, params), precision)
    lse = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt)


def schedule(opt, step):
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def train_reference(conf, seed, batches, precision="f32"):
    """Three (or ``len(batches)``) AdamW steps from the seed's weights.

    ``batches``: host (tokens, labels) pairs of shape (B, S).  The loss is
    the mean token NLL; gradients are summed over blocks of one row per
    device.  Returns the losses, and per leaf (``leaf_norms``) the norms of
    the first step's clipped gradient and of the parameters' change after
    the last step."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    opt = conf["optimizer"]
    # one row per device, the parameters on every device: on several
    # chips the rows run side by side and their sums add up
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("rows",))
    rep, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    rows = len(devs)
    params = weights.make(shapes(conf), seed, rep)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    grad_rows = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_sum(conf, p, t, l, precision)),
        in_shardings=(rep, by_row, by_row), out_shardings=(rep, rep))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    losses, first_grad = [], None
    for step, (tok, lab) in enumerate(batches, start=1):
        n_tok = tok.size
        total, grads = 0.0, None
        for r in range(0, tok.shape[0], rows):
            ls, g = grad_rows(params, tok[r:r + rows], lab[r:r + rows])
            total += float(ls)
            grads = g if grads is None else add(grads, g)
        losses.append(total / n_tok)
        params, m, v, g_used = _adamw(opt, params, grads, m, v, step, n_tok)
        del grads
        if first_grad is None:
            first_grad = leaf_norms(g_used)
        del g_used
    del m, v
    # the same call that made the weights makes them again, bit for bit
    change = jax.tree.map(jnp.subtract, params, weights.make(shapes(conf), seed, rep))
    return losses, first_grad, leaf_norms(change)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _adamw_jit(optitems, params, grads, m, v, step, n_tok):
    opt = dict(optitems)
    grads = jax.tree.map(lambda g: g / n_tok, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = schedule(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"]) + wd * p)
        return p, m, v, g

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def _adamw(opt, params, grads, m, v, step, n_tok):
    return _adamw_jit(tuple(sorted(opt.items())), params, grads, m, v, step, n_tok)


def leaf_norms(tree) -> dict:
    """Euclidean norm of each leaf; a leaf stacked over layers counts as
    one leaf per layer (``stack/0/attn/wq#2``)."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        p = weights._path_str(path)
        if p.startswith("stack/"):
            n = jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                 axis=tuple(range(1, a.ndim))))
            for l, x in enumerate(jax.device_get(n)):
                out[f"{p}#{l}"] = float(x)
        else:
            out[p] = float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
    return out
