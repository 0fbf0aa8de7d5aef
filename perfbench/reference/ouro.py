"""The plain reference of the looped decoder (one stack of layers applied
``total_ut_steps`` times with the same weights, rotary positions, sandwich
norms, an exit gate) trained online by SGD on token rows: forward, objective
and gradients in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. Nothing of the program is
imported.

What is plain here and is not in the program: the loop steps and the layers
are Python loops over one list of layers, rotary positions are cos/sin pairs
of angles worked out in float64, attention is a masked softmax over blocks of
queries against all keys, the objective comes from per-position log-softmax
over blocks of positions and from exit probabilities multiplied out (no
logarithms of them carried), matrix products read float32. Gradients are
taken one layer application at a time (``jax.vjp`` of one layer, from the
last application to the first) and added into one float32 accumulator a
layer; the update is applied when all four loop steps have given theirs, so
that the model at the published widths fits one chip beside its gradients.

Equations. ``h = E[tokens]``. For loop step ``t = 1..T``, for layer ``l =
1..N`` (the same weights at every ``t``): ``a = h + Norm2(Attn(Norm1(h)))``,
``h = a + Norm4(W_down(silu(W_gate u) * (W_up u)))`` with ``u = Norm3(a)``;
``Attn``: ``q, k, v = W_q x, W_k x, W_v x`` per head, ``q`` and ``k`` turned
by ``position * rope_theta^(-j / (head_dim / 2))`` in the pairs ``(j, j +
head_dim / 2)``, causal softmax at ``1/sqrt(head_dim)``, ``W_o``. After the
layers ``h_t = Norm_f(h)``, the next loop step's input, ``logits_t = W_head
h_t``, ``lambda_t = sigmoid(w_g . h_t + b_g)``; a position leaves at step
``t`` with ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` (``t < T``), ``p_T =
prod_{j<T} (1 - lambda_j)``. Objective: the mean over every position of the
valid rows of ``sum_t p_t CE(logits_t, target) - ENTROPY_WEIGHT H(p)``
(position ``i`` predicts token ``i + 1``, the last the row's target). A
forecast is answered from the logits of the first step whose cumulative
``p`` reaches ``early_exit_threshold`` (at 1.0 the last). RMSNorm with
``rms_norm_eps`` everywhere.

``build(config, precision, fault, params)`` returns the stream's reference:
``feed_file`` follows a probe file's events (a launch is ``batchSize`` rows,
one SGD step; a forecast is answered from the model as it stands), and keeps
``losses``, ``answers`` (id, arg-max token, top-two margin), ``fitted``,
``holdout``, ``params``. ``precision="bfloat16"`` keeps the parameters in
bfloat16 (the precision below the configuration's); ``fault`` plants one of
``FAULTS``, each of which leaves out a part of this model.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("one_loop", "no_step_norm", "last_step_loss", "no_entropy", "no_rope",
          "no_output_norms", "half_loss")
ENTROPY_WEIGHT = 0.1
QUERY_BLOCK = 512     # queries a block of the score matrix holds
POSITION_BLOCK = 512  # positions a block of logits holds
TRAIN, FORECAST = 0, 1
GAINS = ("attn_in_norm", "attn_out_norm", "ffn_in_norm", "ffn_out_norm")


def init_laws(arch: dict) -> dict:
    """``(shape, law)`` for every leaf, ``law(rng, shape)`` drawing it in
    float32: matrices (the gate's weights among them) normal(0, 0.02), norm
    gains 1, the gate's bias 0. A leaf of ``layers`` holds all
    ``num_hidden_layers`` of its kind, stacked."""
    f32 = np.float32
    n, d, f, v = arch["num_hidden_layers"], arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    width = arch["num_attention_heads"] * arch["head_dim"]
    normal = lambda rng, shape: f32(0.02) * rng.standard_normal(shape, dtype=f32)
    ones = lambda rng, shape: np.ones(shape, f32)
    zeros = lambda rng, shape: np.zeros(shape, f32)
    layers = {name: ((n, d), ones) for name in GAINS}
    layers.update(wq=((n, d, width), normal), wk=((n, d, width), normal), wv=((n, d, width), normal),
                  wo=((n, width, d), normal), w_gate=((n, d, f), normal), w_up=((n, d, f), normal),
                  w_down=((n, f, d), normal))
    return {"embed": ((v, d), normal), "layers": layers, "norm": ((d,), ones), "head": ((d, v), normal),
            "gate": {"w": ((d,), normal), "b": ((1,), zeros)}}


def init_params(arch: dict, seed: int, cap: int = 0) -> dict:
    """Initial weights of the reference's own, drawn from ``init_laws``. The
    control's two sides share them; against the program the reference starts
    from the program's, and a draw of at most ``cap`` elements a leaf (flat)
    is what the program's are held against: their laws, not their values."""
    rng = np.random.default_rng(seed)
    draw = lambda leaf: leaf[1](rng, (min(int(np.prod(leaf[0])), cap),) if cap else leaf[0])
    return jax.tree_util.tree_map(draw, init_laws(arch), is_leaf=lambda x: isinstance(x, tuple))


def init_ranges(arch: dict) -> dict:
    """``{leaf name: (lowest, highest)}`` of the laws above that have bounds: none."""
    return {}


# --- the model ---------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary(x, theta: float):
    """``x [L, H, dh]`` with the pair ``(x_j, x_{j + dh/2})`` of position
    ``m`` turned by the angle ``m * theta^(-j / (dh / 2))``."""
    l, _, dh = x.shape
    half = dh // 2
    angle = np.arange(l, dtype=np.float64)[:, None] * float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    cos = jnp.asarray(np.cos(angle), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def causal_attention(q, k, v):
    """``[L, H, dh]`` each -> ``[L, H, dh]``: masked softmax, a block of
    queries against all keys at a time."""
    l, h, dh = q.shape
    block = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    keys_at = jnp.arange(l)

    @jax.checkpoint
    def one(args):
        q_b, first = args
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / np.sqrt(dh)
        seen = keys_at[None, :] <= (first + jnp.arange(block))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (q.reshape(l // block, block, h, dh), jnp.arange(0, l, block)))
    return out.reshape(l, h, dh)


def layer_forward(arch: dict, fault: Optional[str], p: dict, x):
    """One layer on one row: ``x [L, hidden]`` -> ``[L, hidden]``."""
    eps = arch["rms_norm_eps"]
    l = x.shape[0]
    h, dh = arch["num_attention_heads"], arch["head_dim"]
    u = rms_norm(x, p["attn_in_norm"], eps)
    q, k, v = ((u @ p[w]).reshape(l, h, dh) for w in ("wq", "wk", "wv"))
    if fault != "no_rope":
        q, k = rotary(q, arch["rope_theta"]), rotary(k, arch["rope_theta"])
    mixed = causal_attention(q, k, v).reshape(l, h * dh) @ p["wo"]
    if fault != "no_output_norms":
        mixed = rms_norm(mixed, p["attn_out_norm"], eps)
    a = x + mixed
    u = rms_norm(a, p["ffn_in_norm"], eps)
    ffn = (silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]
    if fault != "no_output_norms":
        ffn = rms_norm(ffn, p["ffn_out_norm"], eps)
    return a + ffn


def cross_entropy(head, h, targets):
    """``-log softmax(h W_head)[target]`` a position, ``[L]``, a block of
    positions at a time."""
    l, d = h.shape
    block = POSITION_BLOCK if l % POSITION_BLOCK == 0 else l

    @jax.checkpoint
    def one(args):
        h_b, t_b = args
        logits = h_b @ head
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        return -jnp.take_along_axis(logp, t_b[:, None], axis=-1)[:, 0]

    return jax.lax.map(one, (h.reshape(l // block, block, d), targets.reshape(l // block, block))).reshape(l)


def exit_probabilities(gate: dict, hs):
    """``p [T, L]`` from the loop steps' outputs ``hs [T, L, hidden]``: the
    gate reads the first ``T - 1``, the last step takes what is left."""
    lam = 1.0 / (1.0 + jnp.exp(-(hs[:-1] @ gate["w"] + gate["b"][0])))
    left = jnp.ones_like(hs[0, :, 0])
    p = []
    for lam_t in lam:
        p.append(lam_t * left)
        left = left * (1.0 - lam_t)
    return jnp.stack(p + [left])


def objective(fault: Optional[str], head, gate, hs, targets, weights):
    """Sum over positions of ``weights * (sum_t p_t CE_t - ENTROPY_WEIGHT
    H(p))``."""
    ce = jnp.stack([cross_entropy(head, h, targets) for h in hs])
    if fault == "last_step_loss":
        return jnp.sum(weights * ce[-1])
    p = exit_probabilities(gate, hs)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    beta = 0.0 if fault == "no_entropy" else ENTROPY_WEIGHT
    return jnp.sum(weights * (jnp.sum(p * ce, axis=0) - beta * entropy))


class Model:
    """The jitted pieces, one layer application at a time. Parameters live
    on the default device in ``param_dtype``, the layers as a list, and are
    read in float32."""

    def __init__(self, arch: dict, precision: str, fault: Optional[str]):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.arch, self.fault = arch, fault
        self.param_dtype = jnp.dtype(precision)
        self.steps = 1 if fault == "one_loop" else int(arch["total_ut_steps"])
        f32 = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
        eps = arch["rms_norm_eps"]
        fwd = functools.partial(layer_forward, arch, fault)
        self._fwd = jax.jit(lambda p, x: fwd(f32(p), x))

        def bwd(p, x, g, acc):
            _, pull = jax.vjp(fwd, f32(p), x)
            g_p, g_x = pull(g)
            return jax.tree_util.tree_map(jnp.add, acc, g_p), g_x

        self._bwd = jax.jit(bwd, donate_argnums=3)
        self._norm = jax.jit(lambda gain, x: rms_norm(x, gain.astype(jnp.float32), eps))

        def norm_bwd(gain, x, g):
            _, pull = jax.vjp(lambda a, b: rms_norm(b, a, eps), gain.astype(jnp.float32), x)
            return pull(g)

        self._norm_bwd = jax.jit(norm_bwd)
        self._tail = jax.jit(lambda head, gate, hs, targets, weights: jax.value_and_grad(
            functools.partial(objective, fault), argnums=(0, 1, 2))(f32(head), f32(gate), hs, targets, weights))
        self._embed_grad = jax.jit(lambda e, tokens, g: jnp.zeros(e.shape, jnp.float32).at[tokens].add(g))
        self._sgd = jax.jit(lambda p, g, lr: jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) - lr * b).astype(self.param_dtype), p, g), donate_argnums=0)
        self._probabilities = jax.jit(lambda gate, hs: exit_probabilities(f32(gate), hs))
        self._logits = jax.jit(lambda head, h: h @ head.astype(jnp.float32))

    def place(self, params: dict) -> dict:
        """A host tree with stacked layers -> the device tree, the layers a list."""
        put = lambda a: jnp.asarray(a, self.param_dtype)
        n = int(self.arch["num_hidden_layers"])
        out = {k: jax.tree_util.tree_map(put, v) for k, v in params.items() if k != "layers"}
        out["layers"] = [{k: put(v[i]) for k, v in params["layers"].items()} for i in range(n)]
        return out

    def host(self, params: dict) -> dict:
        """The device tree on the host in float32, the layers stacked again."""
        host = lambda a: np.asarray(a.astype(jnp.float32))
        out = {k: jax.tree_util.tree_map(host, v) for k, v in params.items() if k != "layers"}
        out["layers"] = {k: np.stack([host(p[k]) for p in params["layers"]]) for k in params["layers"][0]}
        return out

    def steps_outputs(self, params: dict, tokens, kept: Optional[list] = None, pres: Optional[list] = None):
        """``hs [T, L, hidden]``; ``kept`` collects every layer application's
        input and ``pres`` every loop step's output before ``Norm_f``."""
        x = params["embed"][tokens].astype(jnp.float32)
        hs = []
        for _ in range(self.steps):
            for p in params["layers"]:
                if kept is not None:
                    kept.append(x)
                x = self._fwd(p, x)
            if pres is not None:
                pres.append(x)
            hs.append(self._norm(params["norm"], x))
            if self.fault != "no_step_norm":
                x = hs[-1]
        return jnp.stack(hs)

    def logits_after(self, params: dict, tokens) -> np.ndarray:
        """Logits of the position after the row, from the first loop step
        whose cumulative exit probability reaches the threshold."""
        hs = self.steps_outputs(params, tokens)[:, -1:]
        reached = np.cumsum(np.asarray(self._probabilities(params["gate"], hs))[:, 0])
        reached[-1] = 1.0
        step = int(np.argmax(reached >= float(self.arch["early_exit_threshold"])))
        return np.asarray(self._logits(params["head"], hs[step]))[0]

    def sgd_step(self, params: dict, rows: np.ndarray, targets: np.ndarray, lr: float):
        """One SGD step on the mean objective of ``rows [1, L]`` with the
        row's target. Returns the loss."""
        b, l = rows.shape
        if b != 1:
            raise NotImplementedError("the reference steps on one row at a time")
        n_pos = l // 2 if self.fault == "half_loss" else l
        weights = jnp.asarray((np.arange(l) < n_pos) / float(n_pos), jnp.float32)
        tokens = jnp.asarray(rows[0], jnp.int32)
        shifted = jnp.asarray(np.concatenate([rows[0, 1:], targets[:1]]), jnp.int32)
        kept: List = []
        pres: List = []
        hs = self.steps_outputs(params, tokens, kept, pres)
        loss, (g_head, g_gate, g_hs) = self._tail(params["head"], params["gate"], hs, shifted, weights)
        del hs
        zeros = lambda tree: jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
        acc, g_norm = [zeros(p) for p in params["layers"]], zeros(params["norm"])
        g = jnp.zeros_like(g_hs[0])
        for t in reversed(range(self.steps)):
            # a step's output feeds its head and gate and (normed, unless the fault) the next step
            through_norm = g_hs[t] if self.fault == "no_step_norm" else g_hs[t] + g
            d_norm, g_pre = self._norm_bwd(params["norm"], pres.pop(), through_norm)
            g_norm = g_norm + d_norm
            g = g_pre + g if self.fault == "no_step_norm" else g_pre
            for i in reversed(range(len(acc))):
                acc[i], g = self._bwd(params["layers"][i], kept.pop(), g, acc[i])
        grads = {"embed": self._embed_grad(params["embed"], tokens, g), "layers": acc, "norm": g_norm,
                 "head": g_head, "gate": g_gate}
        for name in grads:
            params[name] = self._sgd(params[name], grads[name], lr)
        return float(loss)


# --- the stream --------------------------------------------------------------


class Reference:
    """Follows the probe files' events as the job does: ``batch`` training
    rows are one launch (one SGD step), a file's end launches what is left,
    a forecast is answered from the model as it stands."""

    def __init__(self, config: dict, precision: str, fault: Optional[str],
                 params: Optional[dict] = None):
        learner = config["create"]["learner"]
        self.arch = dict(learner["dataStructure"])
        self.lr = float(learner["hyperParameters"]["learningRate"])
        self.batch = int(config["job_flags"]["batchSize"])
        if self.batch != 1:
            raise NotImplementedError("the reference follows launches of one row")
        self.model = Model(self.arch, precision, fault)
        if params is None:
            params = init_params(self.arch, int(learner["hyperParameters"].get("seed", 0)))
        self.params = self.model.place(params)
        self.losses: List[float] = []
        self.answers: List[tuple] = []  # (forecast id, arg-max token, top-two margin)
        self.fitted = self.holdout = 0

    def feed_file(self, kind: np.ndarray, index: np.ndarray, train_rows, forecast_rows) -> None:
        with jax.default_matmul_precision("highest"):
            for what, i in zip(kind.tolist(), index.tolist()):
                if what == TRAIN:
                    self.losses.append(self.model.sgd_step(
                        self.params, train_rows.tokens[i : i + 1], train_rows.target[i : i + 1], self.lr))
                    self.fitted += 1
                else:
                    logits = self.model.logits_after(self.params, jnp.asarray(forecast_rows.tokens[i], jnp.int32))
                    top = np.argsort(logits)[-2:]
                    self.answers.append((int(i), float(top[1]), float(logits[top[1]] - logits[top[0]])))

    def host_params(self) -> dict:
        return self.model.host(self.params)


def build(config: dict, precision: str = "float32", fault: Optional[str] = None,
          params: Optional[dict] = None) -> Reference:
    return Reference(config, precision, fault, params)
