"""Checkpointing: roundtrip, async, atomicity (tmp never visible), GC,
elastic restore path."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.checkpoint import CheckpointManager


def _tree(x=0.0):
    return {"a": jnp.full((3, 4), 1.0 + x), "b": {"c": jnp.arange(5) + int(x)},
            "step": jnp.int32(7)}


def test_save_restore_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), extra={"data_step": 10})
    step, tree, extra = cm.restore()
    assert step == 10 and extra["data_step"] == 10
    np.testing.assert_array_equal(tree["a"], _tree()["a"])
    np.testing.assert_array_equal(tree["b"]["c"], _tree()["b"]["c"])


def test_async_save_and_keep_gc(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        cm.save_async(s, _tree(s))
    cm.wait()
    assert cm.all_steps() == [3, 4]
    step, tree, _ = cm.restore()
    assert step == 4
    np.testing.assert_array_equal(tree["a"], _tree(4.0)["a"])


def test_no_tmp_dirs_after_save(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(1, _tree())
    assert not list(tmp_path.glob("*.tmp"))


def test_restore_specific_step(tmp_path):
    cm = CheckpointManager(tmp_path, keep=5)
    cm.save(1, _tree(1))
    cm.save(2, _tree(2))
    step, tree, _ = cm.restore(step=1)
    assert step == 1
    np.testing.assert_array_equal(tree["a"], _tree(1.0)["a"])


def test_restore_with_shardings(tmp_path):
    """Elastic path: restore with explicit (single-device) shardings."""
    cm = CheckpointManager(tmp_path)
    cm.save(5, _tree())
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shardings = {"a": sh, "b": {"c": sh}, "step": sh}
    step, tree, _ = cm.restore(shardings=shardings)
    assert tree["a"].sharding == sh


def test_elastic_remesh_subprocess(tmp_path):
    """Elastic re-scaling: checkpoint written on an 8-device (2x4) mesh
    restores onto a 4-device (2x2) mesh with correct values/shardings."""
    import os
    import subprocess
    import sys
    script = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.runtime.checkpoint import CheckpointManager

d = sys.argv[1]
cm = CheckpointManager(d)
mesh8 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
x = jax.device_put(jnp.arange(64.).reshape(8, 8),
                   NamedSharding(mesh8, P("data", "model")))
cm.save(1, {"w": x})
# restore onto a DIFFERENT mesh (first 4 devices)
mesh4 = jax.sharding.Mesh(
    np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
sh = {"w": NamedSharding(mesh4, P("model", "data"))}
step, tree, _ = cm.restore(shardings=sh)
ok = bool(jnp.all(tree["w"] == jnp.arange(64.).reshape(8, 8)))
print(json.dumps({"ok": ok, "ndev": len(tree["w"].sharding.device_set)}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    import json as _json
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["ndev"] == 4


@pytest.mark.slow
def test_trainer_resume(tmp_path):
    """Kill-and-restart: a second Trainer on the same ckpt dir resumes at
    the saved step with identical params."""
    from repro.configs import get_config
    from repro.training.trainer import Trainer, TrainConfig
    cfg = get_config("olmo-1b").smoke()
    t1 = Trainer(cfg, TrainConfig(steps=4, batch_size=2, seq_len=32,
                                  ckpt_dir=str(tmp_path), ckpt_every=2))
    t1.run()
    t2 = Trainer(cfg, TrainConfig(steps=6, batch_size=2, seq_len=32,
                                  ckpt_dir=str(tmp_path), ckpt_every=2))
    assert t2.step == 4                      # resumed, not restarted
    a = jax.tree.leaves(t1.params)[0]
    b = jax.tree.leaves(t2.params)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t2.run()
    assert t2.step == 6
