"""Plain float32 reference of a dense decoder-only transformer's training
steps: forward loss, gradients and AdamW, in straightforward `jax.numpy` at
`Precision.HIGHEST`, with no kernels, remat policies, host streams or
collectives. It imports nothing of the program under test and takes nothing
it made: the weights are drawn here from the seed by the published rule
(one key per parameter leaf, split from `jax.random.key(seed)` in the
leaves' sorted-name order; normal * 0.02, output projections * 0.02 /
sqrt(2 L); biases 0, norm scales 1; each rounded to the dtype the
configuration stores it in), and the token rows come from the benchmark's
feed.

Layer equations (OLMo, StarCoder2 and the like, chosen by the keys of the
configuration file): pre-norm residual blocks x += Attn(Norm(x)),
x += MLP(Norm(x)); Norm is LayerNorm (with or without scale and bias) or
RMSNorm; attention is causal GQA with rotary embeddings on q and k (halves
rotated: [x1 cos - x2 sin, x2 cos + x1 sin], frequencies theta^(-2i/D));
MLP is SwiGLU/GeGLU (act(x Wg) * (x Wu)) Wd or GELU (tanh form) (x Wu) Wd,
with biases where the configuration has them; the head is the transposed
embedding when tied; the loss is the mean token cross-entropy.

It fits one chip at the benchmark's sizes by working layer by layer: the
float32 parameters stay on the device, each layer's input is kept for the
backward, each layer's backward recomputes its own forward (query blocks
and head row blocks are rematerialized), gradients and the AdamW moments
live in host memory and come to the device one layer slice at a time.

`variant` puts a broken or cheaper computation in the program's place:
"fp8" quantizes every matrix product's operands to float8_e4m3fn with a
per-tensor scale (the precision below the configuration's bfloat16),
"half_batch" takes the loss's mean over the first half of the rows only.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512       # query rows per attention block
HEAD_ROWS = 2048    # token rows per block of the output head


def leaf_layout(m: dict):
    """[(name, shape, init, scale, dtype)] in the order the seed's keys are
    drawn: sorted leaf names, the layer stack first. Stacked leaves carry
    a leading layer axis and are named "layers.<group>.<leaf>"."""
    d, h, k, hd, f, v, n_l = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab_size"],
                              m["num_layers"])
    out_scale = 0.02 / math.sqrt(2 * n_l)
    bias, qkv_bias = m["use_bias"], m["use_bias"] or m["qkv_bias"]
    attn = {"wq": ((d, h, hd), "normal", 0.02), "wk": ((d, k, hd), "normal", 0.02),
            "wv": ((d, k, hd), "normal", 0.02), "wo": ((h, hd, d), "normal", out_scale)}
    if qkv_bias:
        attn.update(bq=((h, hd), "zeros", 0), bk=((k, hd), "zeros", 0),
                    bv=((k, hd), "zeros", 0))
    if bias:
        attn["bo"] = ((d,), "zeros", 0)
    gated = m["mlp_act"] in ("swiglu", "geglu")
    ffn = {"w_up": ((d, f), "normal", 0.02), "w_down": ((f, d), "normal", out_scale)}
    if gated:
        ffn["w_gate"] = ((d, f), "normal", 0.02)
    if bias:
        ffn.update(b_up=((f,), "zeros", 0), b_down=((d,), "zeros", 0))
        if gated:
            ffn["b_gate"] = ((f,), "zeros", 0)
    norm = {"rmsnorm": {"scale": ((d,), "ones", 0)},
            "layernorm": {"scale": ((d,), "ones", 0), "bias": ((d,), "zeros", 0)},
            "layernorm_nonparam": {}}[m["norm_type"]]
    layer = {"attn": attn, "ffn": ffn, "ln1": norm, "ln2": norm}
    out = []
    for g in sorted(layer):
        for name in sorted(layer[g]):
            shape, init, scale = layer[g][name]
            dt = "float32" if g.startswith("ln") else "bfloat16"
            out.append((f"layers.{g}.{name}", (n_l,) + shape, init, scale, dt))
    out.append(("embed.embedding", (v, d), "normal", 0.02, "float32"))
    if not m["tie_embeddings"]:
        out.append(("embed.lm_head", (d, v), "normal", 0.02, "bfloat16"))
    for name in sorted(norm):
        shape, init, scale = norm[name]
        out.append((f"final_norm.{name}", shape, init, scale, "float32"))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _init_leaf(key, shape, init, scale, dtype):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype).astype(jnp.float32)


def init_params(m: dict, seed: int, only=None) -> Dict[str, jax.Array]:
    """Every leaf (or the one named `only`) as float32 on the default
    device."""
    layout = leaf_layout(m)
    keys = jax.random.split(jax.random.key(seed), len(layout))
    return {name: _init_leaf(keys[i], shape, init, scale, dt)
            for i, (name, shape, init, scale, dt) in enumerate(layout)
            if only is None or name == only}


# ---------------------------------------------------------------------------
# Layer equations
# ---------------------------------------------------------------------------

def _round8(x):
    """Round to float8_e4m3fn under a per-tensor scale (amax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    """A matrix product's operand in float8: rounded going forward, and its
    gradient rounded the same way going back (per-tensor scaled both ways,
    as float8 training scales them)."""
    return _round8(x)


_q8.defvjp(lambda x: (_round8(x), None), lambda _, ct: (_round8(ct),))


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _norm(m, p, prefix, x):
    eps = m["norm_eps"]
    if m["norm_type"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p[prefix + "scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    if m["norm_type"] == "layernorm":
        out = out * p[prefix + "scale"] + p[prefix + "bias"]
    return out


def _rope(x, theta):
    """x [B, S, H, D]; position of row s is s."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8):
    """Causal GQA softmax attention, q [B,S,H,D], k/v [B,S,K,D]; query head
    h reads kv head h // (H/K). One rematerialized block per Q_BLOCK rows."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, s, kh, h // kh, d) / math.sqrt(d)

    def block(qb, kb, vb, q0):
        sc = _mm("bqkgd,bskd->bkgqs", qb, kb, fp8)
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(kb.shape[1])[None, :] <= qpos, sc, -1e30)
        probs = jax.nn.softmax(sc, axis=-1)
        return _mm("bkgqs,bskd->bqkgd", probs, vb, fp8)

    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        outs.append(jax.checkpoint(block, static_argnums=(3,))(
            qg[:, q0:q1], k[:, :q1], v[:, :q1], q0))
    return jnp.concatenate(outs, 1).reshape(b, s, h, d)


def layer_forward(m, p, x, fp8=False):
    """One decoder layer; p maps "attn.wq"-style names to one layer's
    float32 arrays."""
    h = _norm(m, p, "ln1.", x)
    q = _mm("bsd,dhk->bshk", h, p["attn.wq"], fp8)
    k = _mm("bsd,dhk->bshk", h, p["attn.wk"], fp8)
    v = _mm("bsd,dhk->bshk", h, p["attn.wv"], fp8)
    if "attn.bq" in p:
        q, k, v = q + p["attn.bq"], k + p["attn.bk"], v + p["attn.bv"]
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    o = _mm("bshk,hkd->bsd", _attention(q, k, v, fp8), p["attn.wo"], fp8)
    x = x + o + p.get("attn.bo", 0.0)
    h = _norm(m, p, "ln2.", x)
    u = _mm("bsd,df->bsf", h, p["ffn.w_up"], fp8) + p.get("ffn.b_up", 0.0)
    if m["mlp_act"] == "gelu":
        a = jax.nn.gelu(u, approximate=True)
    else:
        g = _mm("bsd,df->bsf", h, p["ffn.w_gate"], fp8) + p.get("ffn.b_gate", 0.0)
        a = (jax.nn.silu(g) if m["mlp_act"] == "swiglu"
             else jax.nn.gelu(g, approximate=True)) * u
    return x + _mm("bsf,fd->bsd", a, p["ffn.w_down"], fp8) + p.get("ffn.b_down", 0.0)


def head_loss(m, p, x, labels, weights, fp8=False):
    """Final norm, output head and the weighted mean cross-entropy; p holds
    "final_norm.*" and "head" ([V, d] for a tied head, else [d, V])."""
    x = _norm(m, p, "final_norm.", x)
    rows = x.reshape(-1, x.shape[-1])
    lab, w = labels.reshape(-1), weights.reshape(-1)
    tied = m["tie_embeddings"]

    def block(xr, lr, wr, head):
        eq = "nd,vd->nv" if tied else "nd,dv->nv"
        logits = _mm(eq, xr, head, fp8)
        ll = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - ll) * wr)

    total = 0.0
    for r0 in range(0, rows.shape[0], HEAD_ROWS):
        r1 = min(r0 + HEAD_ROWS, rows.shape[0])
        total = total + jax.checkpoint(block)(rows[r0:r1], lab[r0:r1],
                                              w[r0:r1], p["head"])
    return total / jnp.sum(w)


# ---------------------------------------------------------------------------
# Training steps
# ---------------------------------------------------------------------------

def _lr(opt: dict, step: int) -> float:
    """warmup then cosine to a tenth of the peak, at step `step` (0-based)."""
    base, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                         opt["total_steps"])
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def _spaces():
    """(to host memory, to device memory): pinned host memory where the
    device has it, else both are the identity."""
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" not in kinds:
        return (lambda x: x), (lambda x: x)
    host = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    device = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
    return (lambda x: jax.device_put(x, host)), (lambda x: jax.device_put(x, device))


class Reference:
    def __init__(self, model: dict, opt: dict, seed: int, variant: str = "f32"):
        if variant not in ("f32", "fp8", "half_batch"):
            raise ValueError(f"unknown reference variant {variant!r}")
        self.m, self.opt, self.seed, self.variant = model, opt, seed, variant
        self.n_layers = model["num_layers"]
        self.to_host, self.to_device = _spaces()
        fp8 = variant == "fp8"
        m = model

        def layer_at(stack, i, x):
            return layer_forward(m, {k: v[i] for k, v in stack.items()}, x, fp8)

        def layer_bwd(stack, i, x, dy):
            p = {k: v[i] for k, v in stack.items()}
            _, vjp = jax.vjp(lambda p_, x_: layer_forward(m, p_, x_, fp8), p, x)
            dp, dx = vjp(dy)
            return dp, dx, sum(jnp.sum(jnp.square(g)) for g in dp.values())

        def head_vjp(p, x, labels, weights):
            loss, (dp, dx) = jax.value_and_grad(
                lambda p_, x_: head_loss(m, p_, x_, labels, weights, fp8),
                argnums=(0, 1))(p, x)
            return loss, dp, dx

        def embed_grad(dx, tokens, head_grad):
            g = jnp.zeros((m["vocab_size"], dx.shape[-1]), jnp.float32).at[
                tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
            return g + head_grad if m["tie_embeddings"] else g

        def adam(p, g, mu, nu, lr, step, scale):
            b1, b2 = opt["beta1"], opt["beta2"]
            g = g * scale
            mu2 = b1 * mu + (1 - b1) * g
            nu2 = b2 * nu + (1 - b2) * g * g
            new = p - lr * ((mu2 / (1 - b1 ** step))
                            / (jnp.sqrt(nu2 / (1 - b2 ** step)) + 1e-8)
                            + opt["weight_decay"] * p)
            return new, mu2, nu2, jnp.sum(jnp.square(g))

        def adam_slice(p, i, g, mu, nu, lr, step, scale):
            new, mu2, nu2, sq = adam(p[i], g, mu, nu, lr, step, scale)
            return p.at[i].set(new), mu2, nu2, sq

        self._layer = jax.jit(layer_at)
        self._layer_bwd = jax.jit(layer_bwd)
        self._head = jax.jit(head_vjp)
        self._embed_grad = jax.jit(embed_grad)
        self._adam = jax.jit(adam, donate_argnums=0)
        self._adam_slice = jax.jit(adam_slice, donate_argnums=0)
        self._sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))

    def _loss_weights(self, labels):
        w = np.ones(labels.shape, np.float32)
        if self.variant == "half_batch":
            if labels.shape[0] >= 2:
                w[labels.shape[0] // 2:] = 0.0
            else:
                w[:, labels.shape[1] // 2:] = 0.0
        return w

    def grads(self, params, batch):
        """-> (loss, {leaf: gradient}, squared global norm). A stacked
        leaf's gradient is a list of per-layer arrays in host memory."""
        m = self.m
        stack = {k[len("layers."):]: v for k, v in params.items()
                 if k.startswith("layers.")}
        tokens = jnp.asarray(batch["tokens"])
        x = jnp.take(params["embed.embedding"], tokens, axis=0)
        xs = []
        for i in range(self.n_layers):
            xs.append(x)
            x = self._layer(stack, i, x)
        head = {k: v for k, v in params.items() if k.startswith("final_norm.")}
        head["head"] = params["embed.embedding" if m["tie_embeddings"]
                              else "embed.lm_head"]
        loss, dhead, dx = self._head(head, x, jnp.asarray(batch["labels"]),
                                     jnp.asarray(self._loss_weights(
                                         batch["labels"])))
        del x
        g = {k: v for k, v in dhead.items() if k != "head"}
        sq = [self._sq(v) for v in g.values()]
        g_stack = {f"layers.{k}": [None] * self.n_layers for k in stack}
        for i in reversed(range(self.n_layers)):
            dp, dx, sq_i = self._layer_bwd(stack, i, xs[i], dx)
            xs[i] = None
            sq.append(sq_i)
            for k, v in dp.items():
                g_stack[f"layers.{k}"][i] = self.to_host(v)
        g["embed.embedding"] = self._embed_grad(dx, tokens, dhead["head"])
        if not m["tie_embeddings"]:
            g["embed.lm_head"] = dhead["head"]
            sq.append(self._sq(dhead["head"]))
        sq.append(self._sq(g["embed.embedding"]))
        g.update(g_stack)
        return float(loss), g, float(sum(float(v) for v in sq))

    def run(self, batches: List[dict]) -> dict:
        """Train len(batches) steps from the seed's weights. -> {"loss":
        [per step], "grad": {leaf: norm of the first step's clipped
        gradient}, "update": {leaf: norm of the parameters' change}}."""
        params = init_params(self.m, self.seed)
        mu: Dict[str, list] = {}
        nu: Dict[str, list] = {}
        losses, first_grad = [], {}
        for t, batch in enumerate(batches):
            loss, g, sq = self.grads(params, batch)
            losses.append(loss)
            scale = min(1.0, self.opt["grad_clip"] / max(math.sqrt(sq), 1e-9))
            lr, last = _lr(self.opt, t), t == len(batches) - 1
            for name in list(g):
                gi = g.pop(name)
                stacked = isinstance(gi, list)
                parts = gi if stacked else [gi]
                if t == 0:
                    zeros = [jnp.zeros(x.shape, jnp.float32) for x in parts]
                    mu[name], nu[name] = zeros, list(zeros)
                for i, gp in enumerate(parts):
                    args = (self.to_device(gp), self.to_device(mu[name][i]),
                            self.to_device(nu[name][i]), lr, t + 1, scale)
                    if stacked:
                        params[name], m2, v2, sq_i = self._adam_slice(
                            params[name], i, *args)
                        leaf = f"layers.{i}.{name[len('layers.'):]}"
                    else:
                        params[name], m2, v2, sq_i = self._adam(
                            params[name], *args)
                        leaf = name
                    if t == 0:
                        first_grad[leaf] = math.sqrt(float(sq_i))
                    if not last:
                        mu[name][i], nu[name][i] = self.to_host(m2), self.to_host(v2)
                    del m2, v2
        del mu, nu
        update = {}
        for name in list(params):
            start = init_params(self.m, self.seed, name)[name]
            p = params.pop(name)
            if name.startswith("layers."):
                for i in range(self.n_layers):
                    update[f"layers.{i}.{name[len('layers.'):]}"] = math.sqrt(
                        float(self._sq(p[i] - start[i])))
            else:
                update[name] = math.sqrt(float(self._sq(p - start)))
            del p, start
        return {"loss": losses, "grad": first_grad, "update": update}
