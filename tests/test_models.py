"""Model zoo tests: shapes, parameter catalogs, graft entry contract.

Catalog counts are pinned to the reference's fake-model data (reference:
tests/go/fakemodel: resnet50-imagenet has 161 tensors; VGG16 ~138M
params), proving architecture parity without copying size tables.
"""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import (
    MLP,
    SLP,
    BertConfig,
    BertEncoder,
    InceptionV3,
    ResNet18,
    ResNet50,
    VGG16,
    fake_model_catalog,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCatalogs:
    def test_resnet50_catalog_matches_reference(self):
        c = fake_model_catalog("resnet50-imagenet")
        assert len(c) == 161  # reference fakemodel: 161 tensors
        total = sum(c.values())
        assert 25.4e6 < total < 25.8e6  # ResNet-50 ~25.6M params

    def test_vgg16_catalog(self):
        c = fake_model_catalog("vgg16-imagenet")
        total = sum(c.values())
        assert 138e6 < total < 139e6  # VGG16 ~138.4M params

    def test_inception3_catalog(self):
        c = fake_model_catalog("inception3-imagenet")
        total = sum(c.values())
        # InceptionV3 (no aux head) ~23.8M params
        assert 23.6e6 < total < 24.0e6

    def test_fuse_mode(self):
        full = fake_model_catalog("bert-base")
        fused = fake_model_catalog("bert-base", fuse=True)
        assert len(fused) == 1
        assert sum(fused.values()) == sum(full.values())

    def test_unknown_model(self):
        with pytest.raises(ValueError):
            fake_model_catalog("nope")


class TestSmallModels:
    def test_slp_forward(self):
        x = jnp.ones((4, 28, 28, 1))
        model = SLP()
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (4, 10)

    def test_mlp_forward(self):
        x = jnp.ones((4, 28, 28, 1))
        model = MLP()
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (4, 10)


class TestBigModelShapes:
    """eval_shape only — no weights or FLOPs on the test machine."""

    def test_resnet50_output_shape(self):
        model = ResNet50(num_classes=1000)
        out = jax.eval_shape(
            lambda: model.init_with_output(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 224, 224, 3), jnp.float32),
                train=False)[0])
        assert out.shape == (2, 1000)
        assert out.dtype == jnp.float32  # f32 head over bf16 trunk

    def test_vgg16_output_shape(self):
        model = VGG16(num_classes=1000)
        out = jax.eval_shape(
            lambda: model.init_with_output(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 224, 224, 3), jnp.float32),
                train=False)[0])
        assert out.shape == (2, 1000)

    def test_inception3_output_shape(self):
        model = InceptionV3(num_classes=1000)
        out = jax.eval_shape(
            lambda: model.init_with_output(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 299, 299, 3), jnp.float32),
                train=False)[0])
        assert out.shape == (2, 1000)
        assert out.dtype == jnp.float32  # f32 head over bf16 trunk

    def test_bert_output_shape(self):
        cfg = BertConfig(num_layers=2)
        model = BertEncoder(cfg)
        out = jax.eval_shape(
            lambda: model.init_with_output(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 16), jnp.int32))[0])
        assert out.shape == (2, 16, cfg.vocab_size)


class TestGraftEntry:
    def load(self):
        spec = importlib.util.spec_from_file_location(
            "__graft_entry__", os.path.join(REPO, "__graft_entry__.py"))
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        return m

    def test_entry_is_jittable(self):
        m = self.load()
        fn, args = m.entry()
        out = jax.eval_shape(fn, *args)  # trace without compute
        assert out.shape == (8, 1000)

    def test_dryrun_multichip(self):
        m = self.load()
        m.dryrun_multichip(4)  # full SyncSGD step on a 4-device mesh

    def test_dryrun_multichip_nondefault_cpu(self):
        """Regression for round 1's red MULTICHIP check: the dry run must
        stay green when a non-CPU platform owns the default backend (the
        bench host's TPU had a broken libtpu; any array placed on it
        crashed). Run in a subprocess with the conftest's JAX_PLATFORMS=cpu
        pin removed, so whatever accelerator this machine's JAX finds
        becomes the default platform."""
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__ as g; g.dryrun_multichip(4); "
             "print('DRYRUN_GREEN')"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "DRYRUN_GREEN" in proc.stdout

    def test_placement_audit_catches_stray_arrays(self):
        """The audit inside dryrun_multichip must FAIL on any array that
        lands off the dryrun platform — even when that platform is healthy
        and the op succeeds (round 2's failure mode: a stray eager op on
        the default TPU backend succeeded locally but crashed on the
        driver host's mid-upgrade libtpu)."""
        m = self.load()
        devices = jax.devices("cpu")[:2]
        baseline = list(jax.live_arrays())  # strong refs, like dryrun
        x = jnp.ones((4,))  # on-platform array: audit stays green
        m._audit_placements(devices, baseline, "unit")
        # Simulate a foreign-platform dryrun: with allowed={tpu-like}, the
        # CPU-resident array above must trip the audit exactly as a
        # TPU-resident array would trip it for a CPU dryrun.
        class FakeDev:
            platform = "tpu"
        with pytest.raises(AssertionError, match="off the dryrun platform"):
            m._audit_placements([FakeDev()], baseline, "unit")
        del x

    def test_dryrun_devices_probe_rejects_unusable_accelerator(self):
        """A backend that can LIST devices but cannot EXECUTE (the driver
        host's broken libtpu) must be rejected by the probe, falling back
        to virtual CPU devices instead of crashing mid-dryrun."""
        m = self.load()

        class BrokenDevice:
            platform = "fake_accel"

        real_devices = jax.devices

        def fake_devices(platform=None):
            if platform is None:
                return [BrokenDevice() for _ in range(4)] + real_devices(
                    "cpu")
            return real_devices(platform)

        m.jax.devices = fake_devices
        try:
            # device_put onto the fake device raises -> probe fails ->
            # CPU fallback
            devs = m._dryrun_devices(4)
        finally:
            m.jax.devices = real_devices
        assert all(d.platform == "cpu" for d in devs)
