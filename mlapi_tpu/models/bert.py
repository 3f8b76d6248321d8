"""BERT encoder + [CLS] classifier — config 5 of the ladder
(``BASELINE.json:11``: BERT-base on SST-2, batched serving).

A from-scratch TPU-first implementation (no torch, no HF runtime):

- Params are one flat pytree; attention is explicit ``einsum`` over a
  ``[B, L, heads, head_dim]`` layout — XLA fuses QKV projections and
  keeps the big matmuls MXU-shaped.
- Hidden compute in bfloat16 (params/f32 logits/layernorm stats in
  f32), the standard TPU mixed-precision recipe.
- Tensor-parallel layout via ``param_shardings``: QKV/FFN-up kernels
  column-sharded over the ``model`` axis, attention-out/FFN-down
  row-sharded (the Megatron pairing: one all-reduce per block,
  inserted by GSPMD), word embeddings sharded over the vocab dim.
- Weights can be imported from a HuggingFace torch
  ``BertForSequenceClassification`` checkpoint via
  ``params_from_hf_torch`` (logit-parity-tested against torch; SURVEY
  §7 step 7's "silent-accuracy killer" guard).

Dropout is omitted: serving is deterministic, and the ladder's
fine-tuning runs are short enough that it isn't the difference that
matters. (Add stochastic depth later if config 5 fine-tuning
regresses.)

Long-context: ``attention_impl="ring"`` swaps in sequence-parallel
ring attention (``mlapi_tpu.ops.ring_attention``) with the sequence
sharded over the mesh's ``seq`` axis — attention is the only
cross-token op, so the rest of the encoder partitions along L under
GSPMD with no code change.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mlapi_tpu.models import register_model
from mlapi_tpu.utils.platform import pallas_interpret

BERT_PRESETS = {
    # name: (vocab, hidden, layers, heads, intermediate, max_positions)
    "bert-base-uncased": (30522, 768, 12, 12, 3072, 512),
    "bert-large-uncased": (30522, 1024, 24, 16, 4096, 512),
    "bert-tiny": (30522, 128, 2, 2, 512, 512),
}

_LN_EPS = 1e-12  # BERT's layernorm epsilon


def _layer_norm(x, scale, bias):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + _LN_EPS) * scale + bias


@register_model("bert_classifier")
@dataclass(frozen=True)
class BertClassifier:
    """BERT encoder with a pooled-[CLS] classification head."""

    input_kind = "text"  # serving: token ids, not tabular features

    num_classes: int = 2
    bert_preset: str | None = None
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_positions: int = 512
    type_vocab_size: int = 2
    compute_dtype: str = "bfloat16"
    # "full"  = whole-sequence softmax attention on each device;
    # "flash" = fused Pallas kernel (mlapi_tpu.ops.pallas): scores/
    #           softmax/PV stay in VMEM, no [L, L] HBM traffic;
    # "ring"  = sequence-parallel ring attention (mlapi_tpu.ops) with
    #           L sharded over ``mesh``'s ``seq_axis`` (long context).
    attention_impl: str = "full"
    mesh: object | None = None
    seq_axis: str = "seq"

    def __post_init__(self):
        if self.attention_impl not in ("full", "flash", "ring"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}"
            )
        if self.attention_impl == "ring" and self.mesh is None:
            raise ValueError(
                "attention_impl='ring' needs a mesh with a "
                f"{self.seq_axis!r} axis"
            )
        if self.bert_preset is not None:
            v, h, l, a, i, p = BERT_PRESETS[self.bert_preset]
            for name, val in [
                ("vocab_size", v), ("hidden_size", h), ("num_layers", l),
                ("num_heads", a), ("intermediate_size", i),
                ("max_positions", p),
            ]:
                object.__setattr__(self, name, val)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        keys = iter(jax.random.split(rng, 6 + 10 * self.num_layers))

        def dense(k, shape, scale=0.02):
            return {
                "kernel": scale * jax.random.normal(k, shape),
                "bias": jnp.zeros((shape[-1],)),
            }

        params = {
            "embeddings": {
                "word": 0.02 * jax.random.normal(next(keys), (v, h)),
                "position": 0.02 * jax.random.normal(
                    next(keys), (self.max_positions, h)
                ),
                "token_type": 0.02 * jax.random.normal(
                    next(keys), (self.type_vocab_size, h)
                ),
                "ln_scale": jnp.ones((h,)),
                "ln_bias": jnp.zeros((h,)),
            },
            "pooler": dense(next(keys), (h, h)),
            "classifier": dense(next(keys), (h, self.num_classes)),
        }
        for n in range(self.num_layers):
            params[f"layer_{n}"] = {
                "q": dense(next(keys), (h, h)),
                "k": dense(next(keys), (h, h)),
                "v": dense(next(keys), (h, h)),
                "attn_out": dense(next(keys), (h, h)),
                "ln1_scale": jnp.ones((h,)),
                "ln1_bias": jnp.zeros((h,)),
                "ffn_up": dense(next(keys), (h, i)),
                "ffn_down": dense(next(keys), (i, h)),
                "ln2_scale": jnp.ones((h,)),
                "ln2_bias": jnp.zeros((h,)),
            }
        return jax.tree.map(lambda a: a.astype(jnp.float32), params)

    # ------------------------------------------------------------------
    def encode(self, params: dict, token_ids, attention_mask=None):
        """Token ids ``[B, L]`` → hidden states ``[B, L, H]``."""
        cdt = jnp.dtype(self.compute_dtype)
        b, l = token_ids.shape
        if attention_mask is None:
            attention_mask = (token_ids != 0).astype(jnp.int32)

        emb = params["embeddings"]
        x = (
            emb["word"][token_ids]
            + emb["position"][jnp.arange(l)][None, :, :]
            + emb["token_type"][jnp.zeros_like(token_ids)]
        )
        x = _layer_norm(x, emb["ln_scale"], emb["ln_bias"])

        from mlapi_tpu.ops import full_attention, ring_self_attention

        key_mask = attention_mask.astype(jnp.float32)

        nh, hd = self.num_heads, self.head_dim
        for n in range(self.num_layers):
            layer = params[f"layer_{n}"]
            xc = x.astype(cdt)

            def proj(p):
                return (
                    xc @ p["kernel"].astype(cdt) + p["bias"].astype(cdt)
                ).reshape(b, l, nh, hd)

            q, k, v = proj(layer["q"]), proj(layer["k"]), proj(layer["v"])
            if self.attention_impl == "ring":
                ctx = ring_self_attention(
                    self.mesh, q, k, v, key_mask,
                    seq_axis=self.seq_axis, head_axis="model",
                )
            elif self.attention_impl == "flash":
                from mlapi_tpu.ops.pallas import flash_attention_on_mesh

                # Interpreter on the CPU backend only (tests); the
                # compiled Mosaic kernel everywhere else.
                ctx = flash_attention_on_mesh(
                    self.mesh, q, k, v, key_mask,
                    interpret=pallas_interpret(),
                )
            else:
                ctx = full_attention(q, k, v, key_mask)
            ctx = ctx.reshape(b, l, -1)
            attn = ctx @ layer["attn_out"]["kernel"].astype(cdt) + layer[
                "attn_out"
            ]["bias"].astype(cdt)
            x = _layer_norm(
                x + attn.astype(jnp.float32),
                layer["ln1_scale"], layer["ln1_bias"],
            )

            xc = x.astype(cdt)
            up = xc @ layer["ffn_up"]["kernel"].astype(cdt) + layer["ffn_up"][
                "bias"
            ].astype(cdt)
            up = jax.nn.gelu(up.astype(jnp.float32), approximate=False).astype(cdt)
            down = up @ layer["ffn_down"]["kernel"].astype(cdt) + layer[
                "ffn_down"
            ]["bias"].astype(cdt)
            x = _layer_norm(
                x + down.astype(jnp.float32),
                layer["ln2_scale"], layer["ln2_bias"],
            )
        return x

    def apply(self, params: dict, token_ids, attention_mask=None):
        """Token ids ``[B, L]`` → classification logits ``[B, K]``
        (HF ``BertForSequenceClassification`` semantics: tanh pooler
        over the [CLS] hidden state, then the classifier head)."""
        hidden = self.encode(params, token_ids, attention_mask)
        cls = hidden[:, 0, :]
        pooled = jnp.tanh(
            cls @ params["pooler"]["kernel"] + params["pooler"]["bias"]
        )
        return pooled @ params["classifier"]["kernel"] + params["classifier"]["bias"]

    # ------------------------------------------------------------------
    def param_shardings(self, layout=None) -> dict:
        """Megatron-style TP layout; axis names come from the shared
        ``SpecLayout`` (mesh renames touch one place)."""
        from mlapi_tpu.parallel import SpecLayout

        lo = layout or SpecLayout()
        col = {"kernel": lo.attn_qkv(), "bias": lo.bias_col()}
        row = {"kernel": lo.attn_out(), "bias": lo.replicated()}
        rep = lo.replicated()
        specs = {
            "embeddings": {
                "word": lo.embedding_rows(),  # vocab-sharded
                "position": rep,
                "token_type": rep,
                "ln_scale": rep,
                "ln_bias": rep,
            },
            "pooler": {"kernel": rep, "bias": rep},
            "classifier": {"kernel": rep, "bias": rep},
        }
        for n in range(self.num_layers):
            specs[f"layer_{n}"] = {
                "q": dict(col), "k": dict(col), "v": dict(col),
                "attn_out": dict(row),
                "ln1_scale": rep, "ln1_bias": rep,
                "ffn_up": dict(col),
                "ffn_down": dict(row),
                "ln2_scale": rep, "ln2_bias": rep,
            }
        return specs


# ----------------------------------------------------------------------
def params_from_hf_torch(torch_model, model: BertClassifier) -> dict:
    """Convert a HuggingFace torch ``BertForSequenceClassification``
    state dict into this model's param pytree.

    torch ``nn.Linear`` stores ``weight`` as ``[out, in]`` — every
    kernel is transposed on the way in (the classic silent-accuracy
    killer; guarded by the logit-parity test in
    ``tests/test_bert.py``).
    """
    import numpy as np

    sd = {k: np.asarray(v.detach().cpu().numpy()) for k, v in
          torch_model.state_dict().items()}

    def lin(prefix):
        return {
            "kernel": jnp.asarray(sd[f"{prefix}.weight"].T),
            "bias": jnp.asarray(sd[f"{prefix}.bias"]),
        }

    e = "bert.embeddings"
    params = {
        "embeddings": {
            "word": jnp.asarray(sd[f"{e}.word_embeddings.weight"]),
            "position": jnp.asarray(sd[f"{e}.position_embeddings.weight"]),
            "token_type": jnp.asarray(sd[f"{e}.token_type_embeddings.weight"]),
            "ln_scale": jnp.asarray(sd[f"{e}.LayerNorm.weight"]),
            "ln_bias": jnp.asarray(sd[f"{e}.LayerNorm.bias"]),
        },
        "pooler": lin("bert.pooler.dense"),
        "classifier": lin("classifier"),
    }
    for n in range(model.num_layers):
        p = f"bert.encoder.layer.{n}"
        params[f"layer_{n}"] = {
            "q": lin(f"{p}.attention.self.query"),
            "k": lin(f"{p}.attention.self.key"),
            "v": lin(f"{p}.attention.self.value"),
            "attn_out": lin(f"{p}.attention.output.dense"),
            "ln1_scale": jnp.asarray(sd[f"{p}.attention.output.LayerNorm.weight"]),
            "ln1_bias": jnp.asarray(sd[f"{p}.attention.output.LayerNorm.bias"]),
            "ffn_up": lin(f"{p}.intermediate.dense"),
            "ffn_down": lin(f"{p}.output.dense"),
            "ln2_scale": jnp.asarray(sd[f"{p}.output.LayerNorm.weight"]),
            "ln2_bias": jnp.asarray(sd[f"{p}.output.LayerNorm.bias"]),
        }
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)
