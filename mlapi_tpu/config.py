"""Typed config system covering the ``BASELINE.json:6-12`` ladder.

The reference has no config at all — hardcoded filename
(``main.py:19``), hardcoded dataset URL and split in the notebook
(SURVEY §5). Here every training run is described by one
``TrainConfig`` (buildable from YAML or CLI flags), and the five
ladder configs ship as named presets.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any


@dataclass(frozen=True)
class TrainConfig:
    """One training run: model, data, optimization, parallelism."""

    name: str
    model: str
    model_kwargs: dict[str, Any] = field(default_factory=dict)
    dataset: str = "iris"
    dataset_kwargs: dict[str, Any] = field(default_factory=dict)

    steps: int = 500
    batch_size: int | None = None  # None = full batch
    optimizer: str = "adam"
    learning_rate: float = 0.1
    weight_decay: float = 0.0
    seed: int = 0
    eval_every: int = 0

    # Parallelism: mesh shape over (data, model) axes, or THREE dims
    # (data, fsdp, model) to add ZeRO-style parameter/optimizer-state
    # sharding. None = no mesh (single device). (8, 1) = pure DP over
    # 8 chips, (2, 4) = DP x TP, (1, 8, 1) = FSDP over 8 chips
    # (per-device params + AdamW moments drop ~8x; same math,
    # reduce-scatter/all-gather instead of all-reduce). The CLI's
    # --mesh-shape d,f,m overrides per run.
    mesh_shape: tuple[int, ...] | None = None

    checkpoint_dir: str | None = None

    # Knowledge distillation (drafts for speculative decoding): train
    # against a teacher checkpoint's softened logits. ``distill_from``
    # is a checkpoint path — usually given per-run via the CLI's
    # ``--distill-from`` rather than baked into a preset. A preset
    # designed AROUND distillation sets ``distill_required=True`` so
    # running it without a teacher fails loudly instead of silently
    # training a plain hard-label model under a "distilled" name.
    distill_from: str | None = None
    distill_temperature: float = 2.0
    distill_alpha: float = 0.5
    distill_required: bool = False

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["mesh_shape"] = list(self.mesh_shape) if self.mesh_shape else None
        return d

    @classmethod
    def from_json(cls, obj: dict) -> "TrainConfig":
        obj = dict(obj)
        if obj.get("mesh_shape") is not None:
            obj["mesh_shape"] = tuple(obj["mesh_shape"])
        return cls(**obj)

    @classmethod
    def from_yaml(cls, path: str | Path) -> "TrainConfig":
        import yaml

        with open(path) as f:
            return cls.from_json(yaml.safe_load(f))


# --- the ladder (BASELINE.json:6-12) ------------------------------------

_PRESETS: dict[str, TrainConfig] = {}


def register_preset(cfg: TrainConfig) -> TrainConfig:
    if cfg.name in _PRESETS:
        raise ValueError(f"preset {cfg.name!r} already registered")
    _PRESETS[cfg.name] = cfg
    return cfg


def preset_available(cfg: TrainConfig) -> bool:
    """True iff the preset's model and dataset are both registered in
    this build (the ladder lands incrementally; a preset only shows up
    in the CLI once it can actually run)."""
    from mlapi_tpu.datasets import dataset_registered
    from mlapi_tpu.models import model_registered

    return model_registered(cfg.model) and dataset_registered(cfg.dataset)


def get_preset(name: str) -> TrainConfig:
    try:
        cfg = _PRESETS[name]
    except KeyError:
        raise ValueError(
            f"unknown preset {name!r}; available: {sorted(_PRESETS)}"
        ) from None
    if not preset_available(cfg):
        raise ValueError(
            f"preset {name!r} needs model {cfg.model!r} and dataset "
            f"{cfg.dataset!r}, which are not both registered in this build"
        )
    return cfg


def preset_names(*, only_available: bool = True) -> list[str]:
    if not only_available:
        return sorted(_PRESETS)
    return sorted(n for n, c in _PRESETS.items() if preset_available(c))


register_preset(
    TrainConfig(
        name="iris-linear",
        model="linear",
        model_kwargs={"num_features": 4, "num_classes": 3},
        dataset="iris",
        steps=500,
        learning_rate=0.1,
        weight_decay=1e-3,
    )
)

register_preset(
    TrainConfig(
        name="mnist-softmax",
        model="linear",
        model_kwargs={"num_features": 784, "num_classes": 10},
        dataset="mnist",
        steps=2000,
        batch_size=256,
        learning_rate=1e-3,
        eval_every=500,
    )
)

register_preset(
    TrainConfig(
        name="fashion-mlp",
        model="mlp",
        model_kwargs={
            "num_features": 784,
            "num_classes": 10,
            "hidden_dims": [256, 128],
        },
        dataset="fashion_mnist",
        steps=3000,
        batch_size=256,
        learning_rate=1e-3,
        eval_every=500,
        mesh_shape=(8, 1),  # pure data-parallel over a v5e-8
    )
)

# Real-data anchors for the configs 2-3 model families: the MNIST /
# Fashion-MNIST files cannot be fetched in this air-gapped build, so
# the same linear / MLP architectures also train on the REAL
# handwritten-digits scans scikit-learn bundles (datasets/digits.py) —
# published accuracies that mean something, next to the clearly-marked
# synthetic rows.
register_preset(
    TrainConfig(
        name="digits-softmax",
        model="linear",
        model_kwargs={"num_features": 64, "num_classes": 10},
        dataset="digits",
        steps=2000,
        batch_size=256,
        learning_rate=1e-3,
        eval_every=500,
    )
)

register_preset(
    TrainConfig(
        name="digits-mlp",
        model="mlp",
        model_kwargs={
            "num_features": 64,
            "num_classes": 10,
            "hidden_dims": [256, 128],
        },
        dataset="digits",
        steps=3000,
        batch_size=256,
        learning_rate=1e-3,
        eval_every=500,
        mesh_shape=(8, 1),
    )
)

register_preset(
    TrainConfig(
        name="criteo-widedeep",
        model="wide_deep",
        model_kwargs={
            "num_dense": 13,
            "vocab_sizes": [100_000] * 26,
            "embed_dim": 16,
            "hidden_dims": [256, 128],
            "num_classes": 2,
        },
        dataset="criteo",
        steps=2000,
        batch_size=1024,
        # TRUE-sparse rowwise-AdaGrad tables + AdamW dense
        # (train/sparse_embed.py): gradients w.r.t. gathered rows and
        # scatter updates of touched rows ONLY — the dense [F, V, D]
        # cotangent and full-table optimizer sweep (the step's
        # dominant HBM traffic) never
        # materialize. Numerically IDENTICAL trajectory to the dense
        # recsys-adamw it replaces (tests/test_sparse_embed.py pins
        # leaf-for-leaf equality), measured 8.9x step time on CPU at
        # this exact config (220.5 -> 24.8 ms/step); the r04 dense
        # convergence numbers therefore stand unchanged (400 steps:
        # 0.5481 vs dense-AdamW 0.5442 test acc).
        optimizer="recsys-sparse-adamw",
        learning_rate=1e-3,
        eval_every=500,
        mesh_shape=(2, 4),  # DP x model-sharded embeddings
    )
)

register_preset(
    TrainConfig(
        name="sst2-bert",
        model="bert_classifier",
        # attention_impl="flash": the in-house Pallas kernel. Full
        # attention materializes [B, H, L, L] scores per layer — at
        # batch 128 that is the dominant HBM traffic and why MFU FELL
        # with batch size (0.503@32 -> 0.486@128, r03); the flash
        # kernel keeps scores in VMEM tiles, so the flagship training
        # config now exercises the kernel the repo built for it.
        model_kwargs={
            "bert_preset": "bert-base-uncased", "num_classes": 2,
            "attention_impl": "flash",
        },
        dataset="sst2",
        steps=3000,
        batch_size=32,
        optimizer="adamw",
        learning_rate=2e-5,
        eval_every=500,
        mesh_shape=(2, 4),  # DP x TP
    )
)

# Config-5 real-data proxy: BERT text classification on 100% real
# local prose (repo docs windows labeled by source file — see
# datasets/docs_clf.py). Same task shape as SST-2, every byte real;
# the residual gap (pretrained weights + GLUE labels) is what
# --from-hf closes when a local HF checkpoint exists.
register_preset(
    TrainConfig(
        name="docsclf-bert",
        model="bert_classifier",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 64, "num_layers": 2,
            "num_heads": 4, "intermediate_size": 128,
            "max_positions": 128, "num_classes": 4,
        },
        dataset="docs_clf",
        dataset_kwargs={"seq_len": 128},
        steps=300,
        batch_size=64,
        optimizer="adamw",
        learning_rate=1e-3,
        eval_every=100,
    )
)

# Decoder-family LM presets: next-token training on the repo's own
# documentation (datasets/textlm.py — real English prose, zero-egress),
# producing checkpoints that serve via /generate. These demonstrate the
# full generative pipeline (corpus -> fit -> checkpoint -> serving);
# the corpus is ~50k tokens, so they train in seconds, not to quality.
register_preset(
    TrainConfig(
        name="docs-gpt",
        model="gpt_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 128, "num_layers": 2,
            "num_heads": 4, "max_positions": 256,
            "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=300,
        batch_size=64,
        optimizer="adamw",
        learning_rate=3e-4,
        eval_every=100,
    )
)

# Speculative-decoding draft for docs-gpt: same tokenizer/corpus,
# ~1/10th the weights. Train both and serve with
#   python -m mlapi_tpu.serving --checkpoint <docs-gpt ckpt> \
#       --draft-checkpoint <docs-gpt-draft ckpt>
register_preset(
    TrainConfig(
        name="docs-gpt-draft",
        model="gpt_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 48, "num_layers": 1,
            "num_heads": 4, "max_positions": 256,
            "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=300,
        batch_size=64,
        optimizer="adamw",
        learning_rate=1e-3,
        eval_every=100,
    )
)

# DISTILLED draft for docs-gpt: same serving-side contract as
# docs-gpt-draft, but trained against the target's softened logits
# (pass --distill-from <docs-gpt ckpt>). A hard-label draft agrees
# with the target only where the data forces it; a distilled draft
# matches the target's own distribution — the quantity speculative
# acceptance actually tests — which is what moves acceptance (0.31-
# 0.46 on the independent pair) toward useful territory.
register_preset(
    TrainConfig(
        name="docs-gpt-draft-distilled",
        model="gpt_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 48, "num_layers": 1,
            "num_heads": 4, "max_positions": 256,
            "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=600,
        batch_size=64,
        optimizer="adamw",
        learning_rate=1e-3,
        eval_every=200,
        distill_temperature=2.0,
        distill_alpha=0.1,  # mostly match the teacher, lightly ground
        distill_required=True,
    )
)

register_preset(
    TrainConfig(
        name="docs-llama",
        model="llama_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 128, "num_layers": 2,
            "num_heads": 4, "num_kv_heads": 2, "max_positions": 256,
            "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=300,
        batch_size=64,
        optimizer="adamw",
        learning_rate=3e-4,
        eval_every=100,
    )
)

# The Kimi-Linear family at CPU widths: five layers of the published
# kinds (KDA + dense, KDA, KDA, MLA, KDA; the last four with the
# sparse-expert FFN), 16 experts with 4 a token of which this model
# holds the first 8 (``experts_held``: the layer expert parallelism
# needs, run without its exchange). Training only: the serving CLI
# refuses the checkpoint.
register_preset(
    TrainConfig(
        name="docs-kimi-linear",
        model="kimi_linear_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 64, "num_layers": 5,
            "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
            "first_k_dense_replace": 1, "intermediate_size": 256,
            "num_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "kv_lora_rank": 32,
            "kda_num_heads": 4, "kda_head_dim": 16, "kda_chunk": 32,
            "num_experts": 16, "num_experts_per_token": 4,
            "moe_intermediate_size": 32, "experts_held": [0, 8],
            "moe_tile": 32, "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=200,
        batch_size=16,
        optimizer="adamw",
        learning_rate=1e-3,
        eval_every=100,
    )
)

# The Laguna family at CPU widths: five layers of the published kinds
# (full attention + dense, three sliding-window layers and one full
# layer over sparse experts), 6 / 8 query heads over 2 K/V heads, a
# window shorter than the row, partial YaRN rotary on the full layers,
# 16 experts with 4 a token of which this model holds the first 8.
# Training only: the serving CLI refuses the checkpoint.
register_preset(
    TrainConfig(
        name="docs-laguna",
        model="laguna_lm",
        model_kwargs={
            "vocab_size": 260, "hidden_size": 64, "num_layers": 5,
            "layer_types": ["full_attention", "sliding_attention",
                            "sliding_attention", "sliding_attention",
                            "full_attention"],
            "heads_per_layer": [6, 8, 8, 8, 6],
            "mlp_layer_types": ["dense", "sparse", "sparse", "sparse",
                                "sparse"],
            "num_kv_heads": 2, "head_dim": 16, "sliding_window": 32,
            "intermediate_size": 256, "num_experts": 16,
            "num_experts_per_tok": 4, "moe_intermediate_size": 32,
            "shared_expert_intermediate_size": 32,
            "experts_held": [0, 8], "moe_tile": 32,
            "compute_dtype": "float32",
        },
        dataset="docs_text",
        dataset_kwargs={"seq_len": 128},
        steps=200,
        batch_size=16,
        optimizer="adamw",
        learning_rate=1e-3,
        eval_every=100,
    )
)
