"""``child_common.mesh_or_none``: the mesh a training child hands to
``fit``. Every configuration on one device gets None, so that its
``fit`` call is the unsharded program it always was (a (1, 1) mesh
would be another program); the four-chip configuration gets its
(4, 1) mesh over the first four devices, and a child that sees fewer
devices than the configuration's ``chips`` stops as without a chip."""

import glob
import json
import os
import subprocess
import sys

import pytest

import harness
from child_common import NO_CHIP, mesh_or_none


def configs():
    out = {}
    for path in sorted(glob.glob(os.path.join(harness.BENCH_DIR, "configs",
                                              "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-5]] = json.load(f)
    return out


def test_every_one_device_configuration_gets_no_mesh():
    one = {k: c for k, c in configs().items() if c["mesh"] == [1, 1]}
    assert {"bert-base-sst2", "kimi-linear-48b-a3b-ep32",
            "laguna-xs2-ep8"} <= set(one)
    for name, cfg in one.items():
        assert cfg["chips"] == 1, name
        assert mesh_or_none(cfg) is None, name


@pytest.mark.parametrize("devices,want", [(8, "(4, 1) 0,1,2,3"), (1, None)])
def test_the_four_chip_configuration(devices, want):
    code = (
        "import sys, json; sys.path[:0] = [{b!r}, {r!r}]\n"
        "from child_common import mesh_or_none\n"
        "cfg = json.load(open({c!r}))\n"
        "m = mesh_or_none(cfg)\n"
        "print(tuple(m.devices.shape), ','.join(str(d.id) for d in m.devices.flat))\n"
    ).format(b=harness.BENCH_DIR, r=harness.ROOT, c=os.path.join(
        harness.BENCH_DIR, "configs", "bert-base-sst2-dp4.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       capture_output=True, timeout=120)
    if want is None:
        assert r.returncode == NO_CHIP, r.stderr[-2000:]
        assert "configuration needs 4" in r.stdout
    else:
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.split("\n")[-2] == want
