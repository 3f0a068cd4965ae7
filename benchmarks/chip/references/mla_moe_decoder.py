"""Plain float32 reference of a DeepSeek-V3-style decoder's training steps
(Moonlight-16B-A3B): latent attention, a leading dense layer, then layers
of routed and shared SwiGLU experts; forward loss, gradients, the routing
bias rule and AdamW in straightforward `jax.numpy` at `Precision.HIGHEST`.
It imports nothing of the program under test and takes nothing it made.

Weights are drawn from the seed by the published rule: one key per
parameter leaf, split from `jax.random.key(seed)` in the leaves' sorted
path order (the layer stack, the embedding and head, the final norm, the
leading layer); normal * 0.02, output projections (wo, w_down) * 0.02 /
sqrt(2 L); norm scales 1; each rounded to the dtype the configuration
stores it in. Stacked leaves are named "layers.<group>.<leaf>" with a
leading layer axis, the leading layer's "lead.<group>.<leaf>".

Layer equations (x += Attn(RMSNorm(x)), x += FFN(RMSNorm(x))):
  attention  q = h wq (heads x (nope + rope)); c = RMSNorm(h wkv_a) * s;
             k_pe = h wk_rope, one for all heads; [k_nope; v] = c wkv_b;
             RoPE (halves rotated, theta^(-2i/64)) on q_pe and k_pe;
             causal softmax(q.k / sqrt(nope + rope)) v, then wo;
  lead FFN   SwiGLU (silu(h wg) * (h wu)) wd;
  MoE FFN    s = sigmoid(h router) over all experts; the top k by s + b,
             b the layer's selection bias; weights s_i / sum_top s *
             scale; each HELD expert computed densely on every token
             times its weight (zero where not picked): the absent
             experts' part is left out; plus the shared SwiGLU.
  loss       mean token cross-entropy + alpha * sum over MoE layers of
             the sequence-wise balance loss sum_i f_i P_i (f_i = E/(k S)
             x picks of expert i in a sequence, P_i its mean normalised
             score), averaged over sequences.
  bias rule  after each step b += gamma * sign(mean load - load), loads
             the step's picks per expert.

It fits one chip by working layer by layer as dense_decoder.py does:
float32 parameters on the device, each layer's input kept for the
backward, each layer's backward recomputing its forward (query blocks and
experts rematerialized), gradients and AdamW moments in host memory.

`variant`: "fp8" quantizes every matrix product's operands to
float8_e4m3fn (per-tensor scale), "half_batch" takes the loss's mean over
the first half of the rows only (dense_decoder.py's two).
"""
from __future__ import annotations

import importlib.util
import math
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _dense():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dense_decoder.py")
    spec = importlib.util.spec_from_file_location("chip_ref_dense", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_d = _dense()
_mm, _norm, _rope, _lr, _spaces = _d._mm, _d._norm, _d._rope, _d._lr, _d._spaces
head_loss, _init_leaf = _d.head_loss, _d._init_leaf
Q_BLOCK = _d.Q_BLOCK


def _attn_layout(m: dict, out_scale: float):
    d, h, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dr, dv = m["qk_rope_dim"], m["v_head_dim"]
    dn = m["head_dim"] - dr
    return {"kv_norm": ((r,), "ones", 0, "float32"),
            "wk_rope": ((d, dr), "normal", 0.02, "bfloat16"),
            "wkv_a": ((d, r), "normal", 0.02, "bfloat16"),
            "wkv_b": ((r, h, dn + dv), "normal", 0.02, "bfloat16"),
            "wo": ((h, dv, d), "normal", out_scale, "bfloat16"),
            "wq": ((d, h * (dn + dr)), "normal", 0.02, "bfloat16")}


def _swiglu_layout(d: int, f: int, out_scale: float):
    return {"w_down": ((f, d), "normal", out_scale, "bfloat16"),
            "w_gate": ((d, f), "normal", 0.02, "bfloat16"),
            "w_up": ((d, f), "normal", 0.02, "bfloat16")}


def leaf_layout(m: dict):
    """[(name, shape, init, scale, dtype)] in the order the seed's keys are
    drawn."""
    d, f, n_l = m["d_model"], m["d_ff"], m["num_layers"]
    held, e = m["experts_held"] or m["num_experts"], m["num_experts"]
    out_scale = 0.02 / math.sqrt(2 * n_l)
    norm = {"scale": ((d,), "ones", 0, "float32")}
    moe = {"router": ((d, e), "normal", 0.02, "float32"),
           "w_down": ((held, f, d), "normal", out_scale, "bfloat16"),
           "w_gate": ((held, d, f), "normal", 0.02, "bfloat16"),
           "w_up": ((held, d, f), "normal", 0.02, "bfloat16")}
    layer = {"attn": _attn_layout(m, out_scale), "ffn": moe, "ln1": norm,
             "ln2": norm,
             "shared": _swiglu_layout(d, m["shared_experts"] * f, out_scale)}
    lead = {"attn": _attn_layout(m, out_scale),
            "ffn": _swiglu_layout(d, m["dense_d_ff"], out_scale),
            "ln1": norm, "ln2": norm}
    n_stack = n_l - m["first_dense_layers"]
    out = []
    for g in sorted(layer):
        for name in sorted(layer[g]):
            shape, init, scale, dt = layer[g][name]
            out.append((f"layers.{g}.{name}", (n_stack,) + shape, init, scale,
                        dt))
    out.append(("embed.embedding", (m["vocab_size"], d), "normal", 0.02,
                "float32"))
    out.append(("embed.lm_head", (d, m["vocab_size"]), "normal", 0.02,
                "bfloat16"))
    out.append(("final_norm.scale", (d,), "ones", 0, "float32"))
    for g in sorted(lead):
        for name in sorted(lead[g]):
            shape, init, scale, dt = lead[g][name]
            out.append((f"lead.{g}.{name}", shape, init, scale, dt))
    return out


def init_params(m: dict, seed: int, only=None) -> Dict[str, jax.Array]:
    layout = leaf_layout(m)
    keys = jax.random.split(jax.random.key(seed), len(layout))
    return {name: _init_leaf(keys[i], shape, init, scale, dt)
            for i, (name, shape, init, scale, dt) in enumerate(layout)
            if only is None or name == only}


# ---------------------------------------------------------------------------
# Layer equations
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _causal(q, k, v, fp8):
    """Causal softmax attention, q/k [B,S,H,D], v [B,S,H,Dv], one
    rematerialized block of Q_BLOCK query rows at a time."""
    s, d = q.shape[1], q.shape[-1]

    def block(qb, kb, vb, q0):
        sc = _mm("bqhd,bkhd->bhqk", qb, kb, fp8) / math.sqrt(d)
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(kb.shape[1])[None, :] <= qpos, sc, -1e30)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vb, fp8)

    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        outs.append(jax.checkpoint(block, static_argnums=(3,))(
            q[:, q0:q1], k[:, :q1], v[:, :q1], q0))
    return jnp.concatenate(outs, 1)


def attention(m, p, x, fp8=False):
    """x + the latent attention block; p maps "attn.wq"-style names."""
    b, s, _ = x.shape
    h, dr = m["num_heads"], m["qk_rope_dim"]
    dn = m["head_dim"] - dr
    y = _rms(x, p["ln1.scale"], m["norm_eps"])
    q = _mm("bsd,dk->bsk", y, p["attn.wq"], fp8).reshape(b, s, h, dn + dr)
    c = _rms(_mm("bsd,dr->bsr", y, p["attn.wkv_a"], fp8), p["attn.kv_norm"],
             m["norm_eps"])
    k_pe = _rope(_mm("bsd,dr->bsr", y, p["attn.wk_rope"], fp8)[:, :, None],
                 m["rope_theta"])
    kv = _mm("bsr,rhk->bshk", c, p["attn.wkv_b"], fp8)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])],
                        -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))],
                        -1)
    o = _causal(q, k, kv[..., dn:], fp8)
    return x + _mm("bshk,hkd->bsd", o, p["attn.wo"], fp8)


def _swiglu(y, wg, wu, wd, fp8):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", y, wg, fp8))
               * _mm("td,df->tf", y, wu, fp8), wd, fp8)


def lead_forward(m, p, x, fp8=False):
    x = attention(m, p, x, fp8)
    b, s, d = x.shape
    y = _rms(x, p["ln2.scale"], m["norm_eps"]).reshape(-1, d)
    return x + _swiglu(y, p["ffn.w_gate"], p["ffn.w_up"], p["ffn.w_down"],
                       fp8).reshape(b, s, d)


def moe_forward(m, p, x, bias, fp8=False):
    """-> (x + attention + experts, balance loss, picks per expert [E])."""
    x = attention(m, p, x, fp8)
    b, s, d = x.shape
    e, k = m["num_experts"], m["experts_per_token"]
    held, off = m["experts_held"] or e, m["experts_offset"]
    y = _rms(x, p["ln2.scale"], m["norm_eps"]).reshape(-1, d)
    scores = jax.nn.sigmoid(_mm("td,de->te", y, p["ffn.router"], fp8))
    _, top = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
    top_s = jnp.take_along_axis(scores, top, -1)
    w = top_s / jnp.sum(top_s, -1, keepdims=True) * m["router_scale"]
    onehot = jax.nn.one_hot(top, e, dtype=jnp.float32)             # [T,k,E]
    weight = jnp.einsum("tk,tke->te", w, onehot)                   # [T, E]
    out = _swiglu(y, p["shared.w_gate"], p["shared.w_up"],
                  p["shared.w_down"], fp8)
    expert = jax.checkpoint(lambda y_, wg, wu, wd, c: c[:, None] * _swiglu(
        y_, wg, wu, wd, fp8))
    for i in range(held):
        out = out + expert(y, p["ffn.w_gate"][i], p["ffn.w_up"][i],
                           p["ffn.w_down"][i], weight[:, off + i])
    picks = onehot.sum(1).reshape(b, s, e)
    f = picks.sum(1) * (e / (k * s))
    pn = (scores / jnp.sum(scores, -1, keepdims=True)).reshape(b, s, e)
    aux = jnp.mean(jnp.sum(f * pn.mean(1), -1))
    return x + out.reshape(b, s, d), aux, picks.sum((0, 1))


# ---------------------------------------------------------------------------
# Training steps
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, model: dict, opt: dict, seed: int, variant: str = "f32"):
        if variant not in ("f32", "fp8", "half_batch"):
            raise ValueError(f"unknown reference variant {variant!r}")
        self.m, self.opt, self.seed, self.variant = model, opt, seed, variant
        self.n_stack = model["num_layers"] - model["first_dense_layers"]
        self.to_host, self.to_device = _spaces()
        fp8 = variant == "fp8"
        m, alpha = model, model["router_aux_weight"]

        def stack_at(stack, i):
            return {k: v[i] for k, v in stack.items()}

        def moe_at(stack, i, x, bias):
            y, aux, picks = moe_forward(m, stack_at(stack, i), x, bias, fp8)
            return y, aux, picks

        def moe_bwd(stack, i, x, bias, dy):
            p = stack_at(stack, i)
            _, vjp = jax.vjp(lambda p_, x_: moe_forward(m, p_, x_, bias, fp8)[:2],
                             p, x)
            dp, dx = vjp((dy, jnp.float32(alpha)))
            return dp, dx, sum(jnp.sum(jnp.square(g)) for g in dp.values())

        def lead_bwd(p, x, dy):
            _, vjp = jax.vjp(lambda p_, x_: lead_forward(m, p_, x_, fp8), p, x)
            dp, dx = vjp(dy)
            return dp, dx, sum(jnp.sum(jnp.square(g)) for g in dp.values())

        def head_vjp(p, x, labels, weights):
            loss, (dp, dx) = jax.value_and_grad(
                lambda p_, x_: head_loss(m, p_, x_, labels, weights, fp8),
                argnums=(0, 1))(p, x)
            return loss, dp, dx

        def embed_grad(dx, tokens):
            return jnp.zeros((m["vocab_size"], dx.shape[-1]), jnp.float32).at[
                tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))

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

        self._lead = jax.jit(lambda p, x: lead_forward(m, p, x, fp8))
        self._lead_bwd = jax.jit(lead_bwd)
        self._moe = jax.jit(moe_at)
        self._moe_bwd = jax.jit(moe_bwd)
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

    def grads(self, params, batch, bias):
        """-> (loss, {leaf: gradient}, squared global norm, picks per
        expert [stack layers, E]). A stacked leaf's gradient is a list of
        per-layer arrays in host memory."""
        m = self.m
        alpha = m["router_aux_weight"]
        stack = {k[len("layers."):]: v for k, v in params.items()
                 if k.startswith("layers.")}
        lead = {k[len("lead."):]: v for k, v in params.items()
                if k.startswith("lead.")}
        tokens = jnp.asarray(batch["tokens"])
        x0 = jnp.take(params["embed.embedding"], tokens, axis=0)
        x = self._lead(lead, x0)
        xs, picks, aux = [], [], 0.0
        for i in range(self.n_stack):
            xs.append(x)
            x, a, pk = self._moe(stack, i, x, bias[i])
            aux, picks = aux + float(a), picks + [pk]
        head = {"final_norm.scale": params["final_norm.scale"],
                "head": params["embed.lm_head"]}
        loss, dhead, dx = self._head(head, x, jnp.asarray(batch["labels"]),
                                     jnp.asarray(self._loss_weights(
                                         batch["labels"])))
        del x
        g = {"final_norm.scale": dhead["final_norm.scale"],
             "embed.lm_head": dhead["head"]}
        sq = [self._sq(v) for v in g.values()]
        g_stack = {f"layers.{k}": [None] * self.n_stack for k in stack}
        for i in reversed(range(self.n_stack)):
            dp, dx, sq_i = self._moe_bwd(stack, i, xs[i], bias[i], dx)
            xs[i] = None
            sq.append(sq_i)
            for k, v in dp.items():
                g_stack[f"layers.{k}"][i] = self.to_host(v)
        dp, dx, sq_i = self._lead_bwd(lead, x0, dx)
        sq.append(sq_i)
        g.update({f"lead.{k}": v for k, v in dp.items()})
        g["embed.embedding"] = self._embed_grad(dx, tokens)
        sq.append(self._sq(g["embed.embedding"]))
        g.update(g_stack)
        return (float(loss) + alpha * aux, g,
                float(sum(float(v) for v in sq)), jnp.stack(picks))

    def run(self, batches: List[dict]) -> dict:
        """Train len(batches) steps from the seed's weights. -> {"loss":
        [per step], "grad": {leaf: norm of the first step's clipped
        gradient}, "update": {leaf: norm of the parameters' change}}."""
        m = self.m
        params = init_params(m, self.seed)
        bias = jnp.zeros((self.n_stack, m["num_experts"]), jnp.float32)
        mu: Dict[str, list] = {}
        nu: Dict[str, list] = {}
        losses, first_grad = [], {}
        for t, batch in enumerate(batches):
            loss, g, sq, picks = self.grads(params, batch, bias)
            losses.append(loss)
            bias = bias + m["router_bias_rate"] * jnp.sign(
                jnp.mean(picks, -1, keepdims=True) - picks)
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
                        mu[name][i], nu[name][i] = (self.to_host(m2),
                                                    self.to_host(v2))
                    del m2, v2
        del mu, nu
        update = {}
        for name in list(params):
            start = init_params(m, self.seed, name)[name]
            p = params.pop(name)
            if name.startswith("layers."):
                for i in range(self.n_stack):
                    update[f"layers.{i}.{name[len('layers.'):]}"] = math.sqrt(
                        float(self._sq(p[i] - start[i])))
            else:
                update[name] = math.sqrt(float(self._sq(p - start)))
            del p, start
        return {"loss": losses, "grad": first_grad, "update": update}
