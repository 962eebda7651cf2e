"""Plain float32 reference of cross-silo ACSP-FL fine-tuning of DeepSeek-V2
at one chip's share, and the silos' text it trains on.

Written from the published description (DeepSeek-V2, arXiv:2405.04434, and
its config.json; the ACSP-FL paper's round), with no import from the
program under test. A configuration file (``bench/configs/*.json``) gives
the widths under the config.json's own keys. The block, pre-norm:

- MLA: ``q = h Wq`` (no q_lora) split into ``q_nope`` and ``q_rope``; the
  latent ``c = RMSNorm(h W_dkv)`` and one rope key ``k_rope = h W_kr``
  shared by every head; ``k_nope = c W_uk``, ``v = c W_uv``; YaRN RoPE on
  ``q_rope`` and ``k_rope`` (pairs of adjacent dimensions, frequency i for
  pair i); causal softmax at scale ``(qk_nope + qk_rope) ** -0.5 *
  yarn_mscale(factor, mscale_all_dim) ** 2``; output ``W_o``.
- FFN: a SiLU-GLU of width ``intermediate_size`` in the leading
  ``first_k_dense_replace`` blocks; after them a router over all
  ``published.n_routed_experts`` experts (float32 softmax, greedy top-k,
  gates not renormalised, times ``routed_scaling_factor``), the experts
  this chip holds, and the shared experts as one GLU of width
  ``n_shared_experts * moe_intermediate_size``.
- A final RMSNorm and an untied head over the vocabulary slice.

Departures from the published model, all of them the chip's share or the
recipe: ``num_hidden_layers`` blocks kept of 27; ``n_routed_experts`` (8)
experts held of 64, ids ``[0, 8)``: a token's pairs routed to absent
experts contribute nothing (here each held expert runs on every token,
weighted by its gate where routed and by 0 elsewhere); ``vocab_size``
(12,800) rows of the 102,400 (ids, logits and the loss are over the
slice); no load-balance loss.

The round follows the synchronous ACSP-FL round teacher-forced on the
candidate's selection and share depths (DLD, Eq. 9): each silo trains the
composed model (the first ``pms`` layers global, the rest its own) by
local SGD, its uplink update norm is taken over the shared layers, the
selected silos' shared layers are averaged weighted by their tokens
(masked partial FedAvg; a layer nobody shared keeps its global value),
and every silo is scored on its test sequence with the new global layers
composed with its own. The silos' own models are kept on the host, so the
device holds the global model and one silo's training at a time.

``precision`` is the matmul precision: ``"highest"`` (float32) for the
reference, ``"high"`` (three bfloat16 passes, emulated explicitly so that
it reads the same on any backend) for the control.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# --- the silos' text ------------------------------------------------------------


@dataclasses.dataclass
class Tokens:
    """The attributes ``run_federated`` reads: per silo, ``x`` (C, N, S)
    int32 input ids, ``y`` (C, N, S) next-token targets, ``m`` (C, N) the
    sequences' validity; ``n_samples`` counts the train tokens."""

    x_train: np.ndarray
    y_train: np.ndarray
    m_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    m_test: np.ndarray
    n_classes: int
    name: str = "silo-text"

    @property
    def n_clients(self) -> int:
        return self.x_train.shape[0]

    @property
    def n_features(self) -> int:
        return self.x_train.shape[-1]

    @property
    def n_samples(self) -> np.ndarray:
        return (self.m_train.sum(axis=1) * self.y_train.shape[-1]).astype(np.int32)


def make_tokens(config: dict, seed: int | None = None) -> Tokens:
    """Each silo's sequences: i.i.d. ids from a Zipf law of exponent
    ``zipf_exponent`` over the vocabulary slice, ranked by the silo's own
    permutation of the ids. Fixed by ``data_seed`` unless ``seed`` is
    given."""
    rng = np.random.default_rng(config["data_seed"] if seed is None else seed)
    c, v, s = config["n_clients"], config["vocab_size"], config["sequence_length"]
    n_tr, n_te = config["train_sequences"], config["test_sequences"]
    p = np.arange(1, v + 1, dtype=np.float64) ** -config["zipf_exponent"]
    p /= p.sum()
    seqs = np.stack([
        rng.permutation(v)[rng.choice(v, size=(n_tr + n_te, s + 1), p=p)] for _ in range(c)
    ]).astype(np.int32)
    tr, te = seqs[:, :n_tr], seqs[:, n_tr:]
    return Tokens(tr[..., :-1], tr[..., 1:], np.ones((c, n_tr), bool),
                  te[..., :-1], te[..., 1:], np.ones((c, n_te), bool), n_classes=v)


# --- matmul at a stated precision -----------------------------------------------

def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm3_raw(a, b):
    """float32 product from three bfloat16 passes (hi*hi + hi*lo + lo*hi)."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def _mm3(a, b):
    return _mm3_raw(a, b)


def _mm3_fwd(a, b):
    return _mm3_raw(a, b), (a, b)


def _mm3_bwd(res, g):
    a, b = res
    return _mm3_raw(g, jnp.swapaxes(b, -1, -2)), _mm3_raw(jnp.swapaxes(a, -1, -2), g)


_mm3.defvjp(_mm3_fwd, _mm3_bwd)


def mm(a, b, precision: str):
    """``a @ b`` at ``precision``: ``a`` (..., k) times a matrix ``b`` (k, n),
    or batched over equal leading dims."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        if b.ndim == 2:
            return _mm3(a.reshape(-1, a.shape[-1]), b).reshape(a.shape[:-1] + b.shape[-1:])
        return _mm3(a, b)
    raise ValueError(f"unknown precision {precision!r}")


# --- the model --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Widths:
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    dense: int
    expert: int
    shared: int
    experts: int        # the router's outputs (published)
    held: int
    top_k: int
    norm_topk: bool
    scaling: float
    blocks: int
    first_dense: int
    vocab: int
    eps: float
    theta: float
    yarn: tuple          # (factor, original, beta_fast, beta_slow, mscale, mscale_all_dim)


def widths(config: dict) -> Widths:
    r = config["rope_scaling"]
    return Widths(
        d=config["hidden_size"], heads=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"], rank=config["kv_lora_rank"],
        dense=config["intermediate_size"], expert=config["moe_intermediate_size"],
        shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        experts=config["published"]["n_routed_experts"], held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], norm_topk=config["norm_topk_prob"],
        scaling=float(config["routed_scaling_factor"]),
        blocks=config["num_hidden_layers"], first_dense=config["first_k_dense_replace"],
        vocab=config["vocab_size"], eps=config["rms_norm_eps"], theta=config["rope_theta"],
        yarn=(r["factor"], r["original_max_position_embeddings"], r["beta_fast"],
              r["beta_slow"], r["mscale"], r["mscale_all_dim"]),
    )


def layer_sizes(w: Widths) -> list[int]:
    """Parameters of each layer: embedding, the blocks, final norm + head."""
    mla = (w.d * w.heads * (w.nope + w.rope) + w.d * (w.rank + w.rope) + w.rank
           + w.rank * w.heads * (w.nope + w.v) + w.heads * w.v * w.d)
    dense = 3 * w.d * w.dense
    moe = w.d * w.experts + w.held * 3 * w.d * w.expert + 3 * w.d * w.shared
    blocks = [2 * w.d + mla + (dense if i < w.first_dense else moe) for i in range(w.blocks)]
    return [w.vocab * w.d, *blocks, w.d + w.d * w.vocab]


def init_params(seed: int, w: Widths):
    """Weights from ``seed`` as the configuration's ``assumed.init`` states,
    keyed as ``PRNGKey(seed) -> (init, loop)``; ``init`` split into the
    embedding, one key a block (split into attention and FFN) and the head.
    Returns the layers (a list)."""
    r_init, _ = jax.random.split(jax.random.PRNGKey(seed))
    keys = jax.random.split(r_init, w.blocks + 2)
    sd = 0.02
    normal = jax.random.normal

    def out(key, shape):  # an output projection: N(0, 0.02) / sqrt(2 x blocks)
        return normal(key, shape) * sd / math.sqrt(2 * w.blocks)

    def glu(key, width):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wg": normal(k1, (w.d, width)) * sd, "wu": normal(k2, (w.d, width)) * sd,
                "wd": out(k3, (width, w.d))}

    layers = [{"embed": normal(keys[0], (w.vocab, w.d)) * sd}]
    for i in range(w.blocks):
        k_attn, k_ffn = jax.random.split(keys[i + 1])
        k = jax.random.split(k_attn, 6)
        block = {
            "attn_norm": jnp.ones((w.d,)), "ffn_norm": jnp.ones((w.d,)),
            "wq": normal(k[0], (w.d, w.heads * (w.nope + w.rope))) * sd,
            "wdkv": normal(k[1], (w.d, w.rank)) * sd,
            "wkr": normal(k[2], (w.d, w.rope)) * sd,
            "kv_norm": jnp.ones((w.rank,)),
            "wuk": normal(k[3], (w.rank, w.heads * w.nope)) * sd,
            "wuv": normal(k[4], (w.rank, w.heads * w.v)) * sd,
            "wo": out(k[5], (w.heads * w.v, w.d)),
        }
        if i < w.first_dense:
            block["ffn"] = glu(k_ffn, w.dense)
        else:
            k = jax.random.split(k_ffn, 5)
            block["router"] = normal(k[0], (w.d, w.experts)) * sd
            block["experts"] = {
                "wg": normal(k[1], (w.held, w.d, w.expert)) * sd,
                "wu": normal(k[2], (w.held, w.d, w.expert)) * sd,
                "wd": out(k[3], (w.held, w.expert, w.d)),
            }
            block["shared"] = glu(k[4], w.shared)
        layers.append(block)
    layers.append({"norm": jnp.ones((w.d,)), "head": normal(keys[-1], (w.d, w.vocab)) * sd})
    return layers


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(w: Widths, s: int):
    """cos and sin (S, rope/2) of YaRN's frequencies (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    factor, orig, fast, slow, mscale, mscale_all = w.yarn
    dim = w.rope
    extra = 1.0 / w.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(w.theta))

    low, high = max(math.floor(correction(fast)), 0), min(math.ceil(correction(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    return (jnp.asarray(np.cos(ang) * m, jnp.float32), jnp.asarray(np.sin(ang) * m, jnp.float32))


def rotate(x, cos, sin):
    """x (B, S, ..., rope): pair (2i, 2i+1) rotated by angle i."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    c, s_ = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s_, a * s_ + b * c], axis=-1).reshape(x.shape)


def attention(p, h, w: Widths, precision):
    b, s, _ = h.shape
    q = mm(h, p["wq"], precision).reshape(b, s, w.heads, w.nope + w.rope)
    c = rms_norm(mm(h, p["wdkv"], precision), p["kv_norm"], w.eps)
    k_nope = mm(c, p["wuk"], precision).reshape(b, s, w.heads, w.nope)
    v = mm(c, p["wuv"], precision).reshape(b, s, w.heads, w.v)
    cos, sin = rope_tables(w, s)
    q_rope = rotate(q[..., w.nope:], cos, sin)                 # (B, S, H, rope)
    k_rope = rotate(mm(h, p["wkr"], precision), cos, sin)      # (B, S, rope)
    heads = lambda t: t.transpose(0, 2, 1, 3)                  # noqa: E731 (B, H, S, .)
    k_rope = jnp.broadcast_to(k_rope[:, None], (b, w.heads, s, w.rope))
    scores = (mm(heads(q[..., :w.nope]), heads(k_nope).swapaxes(-1, -2), precision)
              + mm(heads(q_rope), k_rope.swapaxes(-1, -2), precision))
    m = yarn_mscale(w.yarn[0], w.yarn[5])
    scores = scores * (1.0 / math.sqrt(w.nope + w.rope)) * m * m
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm(probs, heads(v), precision).transpose(0, 2, 1, 3).reshape(b, s, w.heads * w.v)
    return mm(out, p["wo"], precision)


def glu(p, h, precision):
    a = mm(h, p["wg"], precision)
    return mm(jax.nn.silu(a) * mm(h, p["wu"], precision), p["wd"], precision)


def experts(p, h, w: Widths, precision):
    """The held experts' part of the routed sum, and the shared experts.
    Returns (y, held pairs, the largest held load / the held mean)."""
    n = h.shape[0] * h.shape[1]
    hf = h.reshape(n, w.d)
    probs = jax.nn.softmax(mm(hf, p["router"], precision), axis=-1)
    gate, idx = jax.lax.top_k(probs, w.top_k)
    if w.norm_topk:
        gate = gate / gate.sum(-1, keepdims=True)

    def expert(y, e):  # expert e on every token, weighted by its gate
        routed = idx == e
        weight = jnp.sum(jnp.where(routed, gate, 0.0), axis=-1)   # (N,)
        ex = {k: p["experts"][k][e] for k in ("wg", "wu", "wd")}
        return y + weight[:, None] * glu(ex, hf, precision), routed.sum()

    y, loads = jax.lax.scan(expert, jnp.zeros_like(hf), jnp.arange(w.held))
    y = y * w.scaling + glu(p["shared"], hf, precision)
    held = loads.sum()
    max_load = jnp.where(held > 0, loads.max() / jnp.maximum(held / w.held, 1e-9), 0.0)
    return y.reshape(h.shape), held, max_load


def sublayer(kind: str, p, x, w: Widths, precision):
    """One residual half of a block, pre-norm: ``attn`` (MLA), ``dense``
    (the GLU) or ``moe`` (the experts). Returns (x, (pairs on held experts,
    largest held load / the held mean))."""
    zero = (jnp.zeros((), jnp.int32), jnp.zeros(()))
    if kind == "attn":
        return x + attention(p, rms_norm(x, p["attn_norm"], w.eps), w, precision), zero
    h = rms_norm(x, p["ffn_norm"], w.eps)
    if kind == "dense":
        return x + glu(p["ffn"], h, precision), zero
    y, held, max_load = experts(p, h, w, precision)
    return x + y, (held, max_load)


ATTN = ("attn_norm", "wq", "wdkv", "wkr", "kv_norm", "wuk", "wuv", "wo")


def sublayers(i: int, p, w: Widths):
    """Block ``i``'s two halves: (kind, the half's parameters)."""
    ffn = "dense" if i < w.first_dense else "moe"
    return [("attn", {k: p[k] for k in ATTN}), (ffn, {k: v for k, v in p.items() if k not in ATTN})]


def head(p, x, y, m, w: Widths, precision):
    """(loss, accuracy) over the valid sequences' tokens of the final norm
    and the head's logits over the vocabulary slice."""
    logits = mm(rms_norm(x, p["norm"], w.eps), p["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    mask = jnp.broadcast_to(m[:, None], y.shape).astype(jnp.float32)
    hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return jnp.sum(nll * mask) / total, jnp.sum(hit * mask) / total


# The pass is taken a residual half-block at a time, each kind of half a
# program of its own, so that the five attention halves share one and the
# four expert halves another, and a chip holds one half's activations at a
# time: the forward keeps each half's input, and the backward recomputes a
# half's forward inside its vector-Jacobian product.

@functools.partial(jax.jit, static_argnames=("kind", "w", "precision"))
def half_fwd(p, x, *, kind, w, precision):
    return sublayer(kind, p, x, w, precision)


@functools.partial(jax.jit, static_argnames=("kind", "w", "precision"))
def half_vjp(p, x, dy, *, kind, w, precision):
    _, vjp = jax.vjp(lambda p, x: sublayer(kind, p, x, w, precision)[0], p, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("w", "precision"))
def head_grad(p, x, y, m, *, w, precision):
    (loss, acc), grads = jax.value_and_grad(
        lambda p, x: head(p, x, y, m, w, precision), argnums=(0, 1), has_aux=True)(p, x)
    return loss, acc, grads


@jax.jit
def embed(table, tokens):
    return table[tokens]


@jax.jit
def embed_grad(table, tokens, dx):
    return jnp.zeros_like(table).at[tokens].add(dx)


@functools.partial(jax.jit, static_argnames=("lr",))
def sgd(p, g, *, lr):
    return jax.tree.map(lambda a, d: a - lr * d, p, g)


def forward(layers, x, w: Widths, precision):
    """Each half-block's input and the last one's output."""
    acts = [embed(layers[0]["embed"], x)]
    for i, p in enumerate(layers[1:-1]):
        for kind, q in sublayers(i, p, w):
            acts.append(half_fwd(q, acts[-1], kind=kind, w=w, precision=precision)[0])
    return acts


def loss_and_grads(layers, x, y, m, *, w, precision):
    """The mean token loss of batch ``x`` -> ``y`` and its gradient."""
    acts = forward(layers, x, w, precision)
    loss, _, (g_head, dx) = head_grad(layers[-1], acts[-1], y, m, w=w, precision=precision)
    grads = [None] * len(layers)
    grads[-1] = g_head
    for i in reversed(range(len(layers) - 2)):
        halves = sublayers(i, layers[i + 1], w)
        g = {}
        for h in (1, 0):
            kind, q = halves[h]
            dq, dx = half_vjp(q, acts[2 * i + h], dx, kind=kind, w=w, precision=precision)
            g.update(dq)
        grads[i + 1] = g
    grads[0] = {"embed": embed_grad(layers[0]["embed"], x, dx)}
    return loss, grads


def sgd_step(layers, x, y, m, *, w, lr, precision):
    """One step of SGD on the mean token loss of batch ``x`` -> ``y``."""
    _, grads = loss_and_grads(layers, x, y, m, w=w, precision=precision)
    return [sgd(p, g, lr=lr) for p, g in zip(layers, grads)]


def evaluate(layers, x, y, m, *, w, precision):
    """The accuracy of the test batch."""
    acts = forward(layers, x, w, precision)
    _, acc, _ = head_grad(layers[-1], acts[-1], y, m, w=w, precision=precision)
    return float(acc)


# --- the round ----------------------------------------------------------------------

def _host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


def _rows(a, n):
    """``a`` padded with zero rows to ``n`` rows (one program for the train
    batches and the test batch)."""
    return np.concatenate([a, np.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)]) if a.shape[0] < n else a


class Run:
    """The reference's state: the global layers on the device, each silo's
    own layers on the host, all starting from the configuration's
    ``init_seed`` weights. ``step`` runs one round."""

    def __init__(self, data: Tokens, recipe: dict, config: dict,
                 precision: str = "highest", fault: str | None = None):
        self.w = widths(config)
        self.g = init_params(config["init_seed"], self.w)
        self.local = [_host(self.g) for _ in range(data.n_clients)]
        self.data, self.recipe = data, recipe
        self.precision, self.fault = precision, fault

    def _compose(self, glob, c, pms):
        return [glob[j] if j < pms else jax.device_put(self.local[c][j])
                for j in range(len(glob))]

    def _train(self, layers, c):
        if self.fault == "unchanged":  # fault: local training returns its input
            return layers
        d, r = self.data, self.recipe
        batch = r["batch_size"]
        for _ in range(r["epochs"]):
            for b in range(max(1, d.x_train.shape[1] // batch)):
                rows = slice(b * batch, (b + 1) * batch)
                m = d.m_train[c, rows]
                if self.fault == "half_batch":  # fault: the second half of each batch left out
                    m = m & (np.arange(m.shape[0]) < m.shape[0] // 2)
                layers = sgd_step(layers, jnp.asarray(d.x_train[c, rows]),
                                  jnp.asarray(d.y_train[c, rows]), jnp.asarray(m),
                                  w=self.w, lr=r["lr"], precision=self.precision)
        return layers

    def step(self, sel, pms):
        """One round teacher-forced on ``sel``/``pms`` (C,); returns the
        silos' (accuracy, update norm), each (C,) numpy. The norm is that of
        the selected silos' (the round's trained models'); NaN elsewhere."""
        c_all = self.data.n_clients
        n_layers = len(self.g)
        weight = np.asarray(self.data.n_samples, np.float64)
        num = [None] * n_layers
        den = np.zeros((n_layers,))
        norm = np.full((c_all,), np.nan)
        for c in np.flatnonzero(sel):
            trained = self._train(self._compose(self.g, c, int(pms[c])), c)
            sq = 0.0
            for j in range(int(pms[c])):
                sq += sum(float(jnp.sum((t - g) ** 2)) for t, g in zip(
                    jax.tree.leaves(trained[j]), jax.tree.leaves(self.g[j])))
                part = jax.tree.map(lambda t: weight[c] * t, trained[j])
                num[j] = part if num[j] is None else jax.tree.map(jnp.add, num[j], part)
                den[j] += weight[c]
            norm[c] = math.sqrt(sq)
            self.local[c] = _host(trained)
            del trained
        self.g = [jax.tree.map(lambda a: a / den[j], num[j]) if den[j] > 0 else self.g[j]
                  for j in range(n_layers)]
        del num
        acc = np.zeros((c_all,))
        d = self.data
        batch = self.recipe["batch_size"]
        for c in range(c_all):
            acc[c] = evaluate(
                self._compose(self.g, c, int(pms[c])), jnp.asarray(_rows(d.x_test[c], batch)),
                jnp.asarray(_rows(d.y_test[c], batch)), jnp.asarray(_rows(d.m_test[c], batch)),
                w=self.w, precision=self.precision)
        if self.fault == "answer":  # fault: silo 0's accuracy answer altered
            acc[0] = 1.0 - acc[0]
        return acc, norm


def run(data, recipe, config, sel, pms, precision="highest", fault=None):
    """Rounds ``0..len(sel)-1``, teacher-forced on ``sel``/``pms`` (T, C).
    Returns (accuracy, update norm), each (T, C) numpy, the norm NaN where
    a silo was not selected."""
    ref = Run(data, recipe, config, precision, fault)
    out = [ref.step(s, p) for s, p in zip(sel, pms)]
    return np.stack([a for a, _ in out]), np.stack([n for _, n in out])
