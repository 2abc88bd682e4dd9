"""The one compile cache (`kungfu_tpu/compile_cache.py`) and what the
launcher hands a worker for its chip slot (`run/job.py`)."""

import os

import pytest

from kungfu_tpu import compile_cache
from kungfu_tpu.plan import PeerID, PeerList, free_port
from kungfu_tpu.run import job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _delta(chip):
    me = PeerID.parse("127.0.0.1:10000")
    return job._worker_env_delta(me, PeerList([me]), 0, "AUTO", None, "",
                                 chip, None)


class TestCacheDir:
    def test_unset_is_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        assert compile_cache.cache_dir() == os.path.join(REPO, ".jax-cache")

    def test_set_from_outside_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.cache_dir() == str(tmp_path)

    @pytest.mark.parametrize("outside", [False, True])
    def test_workers_get_the_same_place(self, monkeypatch, tmp_path,
                                        outside):
        """Never a path under the log directory: the path is part of
        the cache key, and harness log directories are temporary."""
        if outside:
            monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        else:
            monkeypatch.delenv(compile_cache.ENV, raising=False)
        assert _delta(None)[compile_cache.ENV] == compile_cache.cache_dir()

    def test_enable_sets_nothing_when_placed_from_outside(
            self, monkeypatch, tmp_path):
        import jax

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        compile_cache._reset_for_tests()
        try:
            stats = compile_cache.enable()
            assert stats.dir == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
            assert stats.as_dict() == {
                "dir": str(tmp_path), "hits": 0, "misses": 0,
                "skipped": 0, "programs": 0, "nested_traces": 0,
                "trace_s": 0.0,
                "lower_s": 0.0, "backend_s": 0.0, "load_s": 0.0,
                "saved_s": 0.0, "by_fun": {}}
        finally:
            compile_cache._reset_for_tests()


class TestChipSlot:
    def test_slot_is_a_one_chip_slice_of_its_own(self, monkeypatch):
        monkeypatch.delenv("TPU_PROCESS_BOUNDS", raising=False)
        a, b = _delta(0), _delta(3)
        assert (a["TPU_VISIBLE_DEVICES"], b["TPU_VISIBLE_DEVICES"]) \
            == ("0", "3")
        for env in (a, b):
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_MESH_CONTROLLER_ADDRESS"] \
                == "localhost:" + env["TPU_MESH_CONTROLLER_PORT"]
        assert a["TPU_MESH_CONTROLLER_PORT"] \
            != b["TPU_MESH_CONTROLLER_PORT"]

    def test_mesh_controller_port_is_free_not_fixed(self, monkeypatch):
        """libtpu's default port plus the slot collides with a second
        job on the host and with the worker a respawn replaces."""
        import socket

        monkeypatch.delenv("TPU_PROCESS_BOUNDS", raising=False)
        first = int(_delta(2)["TPU_MESH_CONTROLLER_PORT"])
        with socket.socket() as held:   # the old worker still listens
            held.bind(("", first))
            again = int(_delta(2)["TPU_MESH_CONTROLLER_PORT"])
            assert again != first
            with socket.socket() as s:
                s.bind(("", again))

    def test_slots_spawned_together_never_share_a_port(self,
                                                       monkeypatch):
        monkeypatch.delenv("TPU_PROCESS_BOUNDS", raising=False)
        ports = iter([41000, 41000, 41000, 41001])
        monkeypatch.setattr(job, "free_port", lambda: next(ports))
        assert [_delta(i)["TPU_MESH_CONTROLLER_PORT"] for i in (0, 1)] \
            == ["41000", "41001"]

    def test_a_launch_that_lays_itself_out_keeps_its_layout(
            self, monkeypatch):
        monkeypatch.setenv("TPU_PROCESS_BOUNDS", "2,2,1")
        env = _delta(1)
        assert env["TPU_VISIBLE_DEVICES"] == "1"
        assert "TPU_PROCESS_BOUNDS" not in env
        assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in env

    def test_no_slot_no_tpu_settings(self):
        assert not [k for k in _delta(None) if k.startswith("TPU_")]


def test_free_ports_are_bindable_and_fresh():
    import socket

    port = free_port()
    with socket.socket() as s:
        s.bind(("127.0.0.1", port))
    assert 0 < port < 65536
