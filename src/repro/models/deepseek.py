"""DeepSeek-V2 as a layered model for federated fine-tuning.

The model is a Python list of layers, so that layer sharing (the paper's
K(w, L) and DLD, ``repro.core.layersharing``) sees every block:

    [embedding, block 0 (dense), block 1 .. block n-1 (MoE), final norm + head]

Each block is pre-norm: ``x + MLA(norm(x))``, then ``x + FFN(norm(x))``,
where the FFN is a SiLU-GLU for the leading ``cfg.first_dense`` blocks and
the dropless held-expert layer (``layers.moe_apply_held``) after them. The
layers are unstacked (no scan over periods), which is what keeps the layer
axis visible to layer sharing.

A ``DeepSeekShare`` is what one chip of an expert-parallel group holds of
each layer: every width as the config states it, the router over all
``cfg.n_experts``, ``n_held`` of the routed experts, and a vocabulary of
``cfg.vocab_size`` rows (a slice of the published one: token ids, logits
and the loss are over the slice). It trains with the causal-LM
cross-entropy over the slice and no load-balance loss.

Data is token sequences: ``x`` (B, S) int32 ids, ``y`` (B, S) int32
next-token targets and ``m`` (B,) a per-sequence validity mask; the loss
and accuracy are means over the valid sequences' tokens.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

# the counts each evaluation pass returns, and how a round folds them over
# the clients: (token, expert) pairs routed to held experts, the largest
# held expert's load over the held experts' mean, pairs dropped (none)
COUNTS = (("moe_routed_held", "sum"), ("moe_max_load", "max"), ("moe_dropped", "sum"))


@dataclasses.dataclass(frozen=True)
class DeepSeekShare:
    """One chip's share of a DeepSeek-V2 model (see the module docstring).

    ``cfg.n_layers`` counts the blocks kept, ``cfg.vocab_size`` the
    vocabulary rows held; ``n_held`` routed experts of each MoE layer are
    held, ids ``[0, n_held)``. Each block's activations are recomputed in
    the backward pass, so a chip holds one block's at a time."""

    cfg: ModelConfig
    n_held: int

    # -- parameters --------------------------------------------------------
    def init(self, rng: jax.Array) -> list:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab_size
        dt = jnp.dtype(cfg.dtype)
        keys = jax.random.split(rng, cfg.n_layers + 2)
        params = [{"embed": (jax.random.normal(keys[0], (v, d)) * 0.02).astype(dt)}]
        for i in range(cfg.n_layers):
            k_attn, k_ffn = jax.random.split(keys[i + 1])
            block = {
                "attn_norm": jnp.ones((d,), dt),
                "attn": L.init_mla(k_attn, cfg),
                "ffn_norm": jnp.ones((d,), dt),
            }
            if self.is_moe(i):
                block["moe"] = L.init_moe(k_ffn, cfg, self.n_held)
            else:
                block["ffn"] = L.init_swiglu(k_ffn, cfg)
            params.append(block)
        params.append({
            "norm": jnp.ones((d,), dt),
            "head": (jax.random.normal(keys[-1], (d, v)) * 0.02).astype(dt),
        })
        return params

    def is_moe(self, block: int) -> bool:
        return block >= self.cfg.first_dense

    # -- forward -----------------------------------------------------------
    def _block(self, i: int, p, x, positions):
        cfg = self.cfg
        h, _ = L.mla_attention(p["attn"], L.rms_norm(x, p["attn_norm"], cfg.norm_eps),
                               positions, cfg)
        x = x + h
        h = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if self.is_moe(i):
            y, counts = L.moe_apply_held(p["moe"], h, cfg)
        else:
            y, counts = L.swiglu(p["ffn"], h), None
        return x + y, counts

    def forward(self, params, tokens: jnp.ndarray):
        """``tokens`` (B, S) -> ``(logits (B, S, V) float32, counts)``:
        ``counts`` are the MoE layers' ``moe_routed_held`` (summed) and
        ``moe_max_load`` (largest), and ``moe_dropped`` (0: dropless)."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = jnp.take(params[0]["embed"], tokens, axis=0)
        held = jnp.zeros((), jnp.int32)
        max_load = jnp.zeros((), jnp.float32)
        for i, p in enumerate(params[1:-1]):
            x, counts = jax.checkpoint(self._block, static_argnums=(0,))(i, p, x, positions)
            if counts is not None:
                held = held + counts["held"]
                max_load = jnp.maximum(max_load, counts["max_load"])
        x = L.rms_norm(x, params[-1]["norm"], cfg.norm_eps)
        logits = (x @ params[-1]["head"]).astype(jnp.float32)
        return logits, {"moe_routed_held": held, "moe_max_load": max_load,
                        "moe_dropped": jnp.zeros((), jnp.int32)}

    # -- the federated model's functions -----------------------------------
    @staticmethod
    def _token_mask(y, m):
        return jnp.broadcast_to(m.reshape(m.shape + (1,) * (y.ndim - 1)), y.shape).astype(jnp.float32)

    def _scores(self, params, x, y, m):
        logits, counts = self.forward(params, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        w = self._token_mask(y, m)
        n = jnp.maximum(w.sum(), 1.0)
        return (hit * w).sum() / n, (nll * w).sum() / n, counts

    def loss(self, params, x, y, m) -> jnp.ndarray:
        """Causal-LM cross-entropy over the vocabulary slice, mean over
        the valid sequences' tokens."""
        return self._scores(params, x, y, m)[1]

    def accuracy(self, params, x, y, m) -> jnp.ndarray:
        """Next-token accuracy over the valid sequences' tokens."""
        return self._scores(params, x, y, m)[0]

    def evaluate(self, params, x, y, m):
        """``(accuracy, loss, counts)`` from one forward pass."""
        return self._scores(params, x, y, m)

    def fl_model(self):
        from repro.fl.api import FLModel

        return FLModel(init=self.init, loss=self.loss, accuracy=self.accuracy,
                       evaluate=self.evaluate, counts=COUNTS)


def share(cfg: ModelConfig, n_layers: int, n_held: int, vocab: int) -> DeepSeekShare:
    """The share of ``cfg`` (a published DeepSeek-V2 config) that one chip
    of an expert-parallel group holds, in float32: ``n_layers`` blocks
    (the leading dense ones included), ``n_held`` routed experts of each
    MoE layer and ``vocab`` vocabulary rows; every width as published."""
    if not 0 < n_held <= cfg.n_experts:
        raise ValueError(f"{n_held} held experts of {cfg.n_experts}")
    cut = dataclasses.replace(cfg, n_layers=n_layers, vocab_size=vocab, dtype="float32")
    return DeepSeekShare(cut, n_held=n_held)
