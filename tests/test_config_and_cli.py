"""Config system + train CLI: presets, YAML round trip, end-to-end
train→checkpoint→serve handoff through the CLIs (the capability the
reference implements as notebook → pickle → server, SURVEY §3.4)."""

import dataclasses
import json

import pytest
import yaml

from mlapi_tpu.config import TrainConfig, get_preset, preset_names
from mlapi_tpu.serving import InferenceEngine
from mlapi_tpu.train.__main__ import run as train_run


def test_ladder_presets_registered():
    names = preset_names(only_available=False)
    for expected in (
        "iris-linear",
        "mnist-softmax",
        "fashion-mlp",
        "criteo-widedeep",
        "sst2-bert",
    ):
        assert expected in names
    # Only runnable presets are advertised to the CLI.
    for runnable in preset_names():
        assert runnable in names


def test_yaml_roundtrip(tmp_path):
    cfg = get_preset("fashion-mlp")
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml.safe_dump(cfg.to_json()))
    restored = TrainConfig.from_yaml(p)
    assert restored == cfg
    assert restored.mesh_shape == (8, 1)


def test_unknown_preset_raises():
    with pytest.raises(ValueError, match="unknown preset"):
        get_preset("resnet-imagenet")


def test_train_cli_to_serving_engine(tmp_path):
    """The full handoff: train iris-linear via the CLI entry, load the
    checkpoint into an InferenceEngine, predict."""
    cfg = dataclasses.replace(get_preset("iris-linear"), steps=200)
    out = tmp_path / "ck"
    summary = train_run(cfg, str(out))
    assert summary["test_accuracy"] >= 0.93
    assert (out / "MANIFEST.json").exists()

    engine = InferenceEngine.from_checkpoint(out)
    assert engine.feature_names == (
        "sepal_length", "sepal_width", "petal_length", "petal_width",
    )
    labels, probs = engine.predict_labels([[5.1, 3.5, 1.4, 0.2]])
    assert labels == ["Iris-setosa"]
    assert 0.5 < probs[0] <= 1.0

    manifest = json.loads((out / "MANIFEST.json").read_text())
    assert manifest["config"]["train_config"]["name"] == "iris-linear"


def test_train_cli_mesh_that_does_not_fit(tmp_path):
    """A config demanding more devices than visible: on ONE device
    the run is unsharded (the presets name pod-slice meshes), but
    with several visible it is an error naming --mesh-shape — never a
    quiet run on the first device with the rest idle."""
    import jax

    from mlapi_tpu.parallel import mesh_for_config

    cfg = dataclasses.replace(
        get_preset("iris-linear"), mesh_shape=(64, 1), steps=50
    )
    with pytest.raises(ValueError, match="--mesh-shape"):
        train_run(cfg, None)
    assert mesh_for_config((64, 1), devices=jax.devices()[:1]) is None
    assert mesh_for_config((2, 2)).devices.shape == (2, 2)
