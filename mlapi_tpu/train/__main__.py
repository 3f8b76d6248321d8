"""CLI: train a ladder config and write a serving-ready checkpoint.

Replaces the reference's notebook pipeline (``Logistic
Regression.ipynb``: fetch CSV → fit → pickle.dump) with::

    python -m mlapi_tpu.train --preset iris-linear --out /ckpts/iris
    python -m mlapi_tpu.train --config my_run.yaml --out /ckpts/run1

The written checkpoint contains everything the serving CLI needs
(params + model config + label vocab), closing the train→serve loop:

    python -m mlapi_tpu.serving --checkpoint /ckpts/iris
"""

from __future__ import annotations

import argparse
import json

from mlapi_tpu.config import TrainConfig, get_preset, preset_names
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("train.main")


def run(
    cfg: TrainConfig,
    out: str | None,
    *,
    save_every: int = 0,
    keep_last: int = 0,
    resume: bool = True,
    profile_dir: str | None = None,
    debug_checks: bool = False,
    lora_rank: int = 0,
    init_from: str | None = None,
    from_hf: str | None = None,
) -> dict:
    import jax

    from mlapi_tpu.checkpoint import save_checkpoint
    from mlapi_tpu.datasets import get_dataset
    from mlapi_tpu.models import get_model
    from mlapi_tpu.parallel import initialize_from_env, mesh_for_config
    from mlapi_tpu.parallel.layout import bytes_per_device
    from mlapi_tpu.train import fit
    from mlapi_tpu.utils.platform import device_report

    initialize_from_env()  # multi-host no-op on a single host

    if from_hf and init_from:
        raise ValueError(
            "--from-hf and --init-from both seed the initial weights; "
            "pass exactly one"
        )
    dataset_kwargs = dict(cfg.dataset_kwargs)
    if from_hf:
        # Config-5 readiness: the tokenizer must be the HF dir's OWN
        # WordPiece vocab, or fine-tuned embeddings see the wrong ids.
        # Only datasets whose loader takes a ``tokenizer`` kwarg (the
        # text-classification ones, e.g. sst2) can honour it.
        import inspect
        from pathlib import Path

        from mlapi_tpu.datasets import get_dataset_loader

        vocab_file = Path(from_hf) / "vocab.txt"
        takes_tokenizer = "tokenizer" in inspect.signature(
            get_dataset_loader(cfg.dataset)
        ).parameters
        if vocab_file.exists() and takes_tokenizer:
            from mlapi_tpu.text.tokenizer import WordPieceTokenizer

            dataset_kwargs["tokenizer"] = (
                WordPieceTokenizer.from_vocab_file(vocab_file)
            )
            _log.info("tokenizing with %s", vocab_file)
        elif vocab_file.exists():
            _log.warning(
                "dataset %r does not accept a tokenizer; %s is "
                "ignored and ids may not match the pretrained "
                "embeddings", cfg.dataset, vocab_file,
            )
        else:
            _log.warning(
                "%s has no vocab.txt; falling back to the default "
                "tokenizer — ids may not match the pretrained "
                "embeddings", from_hf,
            )
    splits = get_dataset(cfg.dataset, **dataset_kwargs)
    if splits.source == "synthetic":
        _log.warning(
            "dataset %r is a synthetic stand-in (real files not present); "
            "accuracy numbers are not comparable to published results",
            cfg.dataset,
        )
    model = get_model(cfg.model, **cfg.model_kwargs)
    init_params = None
    if from_hf:
        # Fine-tune from a LOCAL HuggingFace torch checkpoint
        # (zero-egress: local_files_only — this is the path that runs
        # real config 5 the moment bert-base-uncased weights land on
        # disk). Conversion is params_from_hf_torch, logit-parity-
        # tested against the torch reference in tests/test_bert.py.
        from transformers import BertForSequenceClassification

        from mlapi_tpu.models.bert import params_from_hf_torch

        tm = BertForSequenceClassification.from_pretrained(
            from_hf, local_files_only=True,
            num_labels=len(splits.vocab.labels) or 2,
        )
        init_params = params_from_hf_torch(tm, model)
        del tm
        _log.info("initialised from HF torch checkpoint %s", from_hf)
    if init_from:
        # Fine-tune from an existing checkpoint (the model config must
        # match — the tree-signature check inside load_checkpoint
        # refuses a mismatched architecture).
        from mlapi_tpu.checkpoint import load_checkpoint

        # eval_shape: abstract tree only — a full random init of a
        # large pretrained model could OOM before the load even runs.
        abstract = jax.eval_shape(
            lambda: model.init(jax.random.key(cfg.seed))
        )
        init_params, _ = load_checkpoint(init_from, abstract)
        _log.info("initialised from checkpoint %s", init_from)
    if lora_rank:
        # Parameter-efficient fine-tune: adapters train, base freezes
        # (no optimizer moments for it). The final checkpoint is the
        # MERGED plain tree, so serving needs no LoRA awareness.
        # --init-from supplies the pretrained base; without it the
        # base is a fresh init (useful only for tests).
        from mlapi_tpu.models.lora import LoraModel

        model = LoraModel(model, rank=lora_rank)
        init_params = model.init(
            jax.random.key(cfg.seed), base_params=init_params
        )
    if getattr(model, "input_kind", "tabular") == "text":
        # JAX gather clamps out-of-range ids silently; catch a
        # tokenizer/model vocab mismatch before it trains to garbage.
        max_id = int(splits.x_train.max())
        if max_id >= model.vocab_size:
            raise ValueError(
                f"dataset token ids go up to {max_id} but the model's "
                f"embedding table has only {model.vocab_size} rows — "
                "tokenizer and model vocab_size disagree"
            )

    mesh = mesh_for_config(cfg.mesh_shape)

    train_state_dir = cfg.checkpoint_dir or (f"{out}_train_state" if out else None)
    if save_every and not train_state_dir:
        raise ValueError(
            "--save-every needs somewhere to write train state: pass --out "
            "or set checkpoint_dir in the config"
        )
    result = fit(
        model,
        splits,
        steps=cfg.steps,
        batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate,
        weight_decay=cfg.weight_decay,
        optimizer=cfg.optimizer,
        seed=cfg.seed,
        mesh=mesh,
        eval_every=cfg.eval_every,
        checkpoint_dir=train_state_dir if save_every else None,
        save_every=save_every,
        keep_last=keep_last,
        resume=resume,
        profile_dir=profile_dir,
        debug_checks=debug_checks,
        init_params=init_params,
        distill_from=cfg.distill_from,
        distill_temperature=cfg.distill_temperature,
        distill_alpha=cfg.distill_alpha,
    )
    _log.info(
        "%s: %d steps in %.2fs, final_loss=%.4f, test_accuracy=%s",
        cfg.name, result.steps, result.wall_seconds, result.final_loss,
        result.test_accuracy,
    )

    params_out = result.params
    if lora_rank:
        params_out = model.merge_params(result.params)
    if out:
        ckpt_config = {
            "model": cfg.model,
            "model_kwargs": cfg.model_kwargs,
            "feature_names": list(splits.feature_names),
            "train_config": cfg.to_json(),
        }
        if getattr(model, "input_kind", "tabular") == "text":
            # The serving engine must encode requests exactly the way
            # training did: same sequence length, same tokenizer.
            ckpt_config["max_len"] = int(splits.x_train.shape[1])
            if "tokenizer" in splits.extras:
                ckpt_config["tokenizer"] = splits.extras["tokenizer"]
        save_checkpoint(
            out,
            params_out,
            step=result.steps,
            config=ckpt_config,
            vocab=splits.vocab,
        )
        _log.info("checkpoint written to %s", out)

    return {
        "name": cfg.name,
        "steps": result.steps,
        "wall_seconds": result.wall_seconds,
        "host_ms_per_step": result.host_ms_per_step,
        **({"model_stats": result.model_stats} if result.model_stats else {}),
        "first_loss": result.first_loss,
        "final_loss": result.final_loss,
        "test_accuracy": result.test_accuracy,
        "dataset_source": splits.source,
        "checkpoint": out,
        "mesh": list(mesh.devices.shape) if mesh is not None else None,
        "param_bytes_per_device": bytes_per_device(result.params),
        "device": device_report(),
    }


def main(argv=None) -> None:
    from mlapi_tpu.utils.platform import (
        apply_platform_override,
        enable_compile_cache,
    )

    apply_platform_override()
    enable_compile_cache()
    parser = argparse.ArgumentParser("mlapi_tpu.train")
    group = parser.add_mutually_exclusive_group(required=True)
    group.add_argument(
        "--preset", choices=preset_names(), help="a ladder config by name"
    )
    group.add_argument("--config", help="path to a TrainConfig YAML")
    parser.add_argument("--out", help="checkpoint output dir")
    parser.add_argument(
        "--steps", type=int, default=None, help="override config steps"
    )
    parser.add_argument(
        "--mesh-shape", default=None,
        help="override the config's device mesh, comma-separated: "
             "'8,1' = pure DP, '2,4' = DP x TP, and THREE dims "
             "'d,f,m' add a ZeRO/FSDP axis — e.g. '1,8,1' shards "
             "params AND optimizer moments over 8 devices "
             "(per-device state bytes drop ~8x; same math)",
    )
    parser.add_argument(
        "--save-every", type=int, default=0,
        help="checkpoint full train state every N steps (enables resume)",
    )
    parser.add_argument(
        "--keep-last", type=int, default=0,
        help="retain only the newest N committed train-state checkpoints "
             "(0 keeps everything)",
    )
    parser.add_argument(
        "--debug-checks", action="store_true",
        help="compile the step through checkify: NaN/inf anywhere inside "
             "the step raises at the op that produced it (costs a host "
             "sync per step)",
    )
    parser.add_argument(
        "--no-resume", action="store_true",
        help="ignore existing train-state checkpoints",
    )
    parser.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of 20 steps here, from the "
        "6th step this run makes (TensorBoard/XProf; the loop's "
        "fit.step/fit.batch/fit.dispatch spans are on its host plane)",
    )
    parser.add_argument(
        "--lora-rank", type=int, default=0,
        help="LoRA fine-tune at this rank: only low-rank adapters "
             "train (frozen base keeps no optimizer state); the saved "
             "checkpoint is the merged plain tree, served unchanged. "
             "Combine with --init-from to adapt a pretrained model",
    )
    parser.add_argument(
        "--init-from", default=None,
        help="seed training from this committed checkpoint's weights "
             "(full fine-tune, or the frozen base for --lora-rank)",
    )
    parser.add_argument(
        "--from-hf", default=None,
        help="fine-tune from a LOCAL HuggingFace torch BERT "
             "checkpoint dir (config.json + weights [+ vocab.txt, "
             "used for tokenization]); zero-egress — the dir must "
             "already be on disk. This is the real-config-5 path: "
             "--preset sst2-bert --from-hf <bert-base-uncased dir> "
             "with real SST-2 TSVs in $MLAPI_TPU_DATA_DIR/sst2/",
    )
    parser.add_argument(
        "--distill-from", default=None,
        help="knowledge distillation: train against this checkpoint's "
             "softened logits (teacher forward runs inside the jitted "
             "step). The way to train a speculative-decoding draft "
             "that matches its target — e.g. --preset "
             "docs-gpt-draft-distilled --distill-from <docs-gpt ckpt>",
    )
    args = parser.parse_args(argv)

    mesh_shape = None
    if args.mesh_shape:
        try:
            mesh_shape = tuple(int(d) for d in args.mesh_shape.split(","))
        except ValueError:
            parser.error(
                f"--mesh-shape {args.mesh_shape!r} is not a "
                "comma-separated list of integers (e.g. '1,8,1')"
            )
        if len(mesh_shape) not in (2, 3) or any(d < 1 for d in mesh_shape):
            parser.error(
                f"--mesh-shape {args.mesh_shape!r}: need 2 (data,model) "
                "or 3 (data,fsdp,model) positive dimensions"
            )

    cfg = get_preset(args.preset) if args.preset else TrainConfig.from_yaml(args.config)
    import dataclasses

    if args.steps is not None:
        cfg = dataclasses.replace(cfg, steps=args.steps)
    if mesh_shape is not None:
        cfg = dataclasses.replace(cfg, mesh_shape=mesh_shape)
    if args.distill_from is not None:
        cfg = dataclasses.replace(cfg, distill_from=args.distill_from)
    if cfg.distill_required and cfg.distill_from is None:
        parser.error(
            f"preset {cfg.name!r} is a DISTILLATION config: running it "
            "without --distill-from <teacher checkpoint> would silently "
            "train a plain hard-label model under a 'distilled' name"
        )

    summary = run(
        cfg,
        args.out,
        save_every=args.save_every,
        keep_last=args.keep_last,
        resume=not args.no_resume,
        profile_dir=args.profile_dir,
        debug_checks=args.debug_checks,
        lora_rank=args.lora_rank,
        init_from=args.init_from,
        from_hf=args.from_hf,
    )
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
