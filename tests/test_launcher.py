"""Launcher integration tests: real worker subprocesses via kfrun.

The reference validates its launcher by running fake trainers under
`kungfu-run -H 127.0.0.1:np` (SURVEY §4 tier 4); same here: kfrun spawns
real processes on loopback ports, and we assert on exit codes and worker
logs. Config server + schedule units are covered here too.
"""

import os
import subprocess
import sys
import urllib.request

import pytest

from kungfu_tpu.elastic import ConfigServer, step_based_schedule
from kungfu_tpu.elastic.schedule import parse_schedule
from kungfu_tpu.peer import Stage, fetch_url, put_url
from kungfu_tpu.plan import Cluster, HostList

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(REPO, "tests", "workers")


def run_kfrun(args, worker, timeout=90, extra_env=None, port_base=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("KF_TIMEOUT_MS", "30000")
    env["KF_LOG_LEVEL"] = "warn"
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "kungfu_tpu.run", *args, "--",
           sys.executable, os.path.join(WORKERS, worker)]
    return subprocess.run(
        cmd, cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


class TestSimpleMode:
    @pytest.mark.parametrize("np_", [1, 2, 4])
    def test_fake_trainer(self, np_, tmp_path):
        r = run_kfrun(
            ["-np", str(np_), "-H", f"127.0.0.1:{np_}",
             "-port-range", "26000-26999",
             "-logdir", str(tmp_path), "-q"],
            "fake_trainer.py",
        )
        assert r.returncode == 0, r.stderr[-2000:]
        logs = "".join(
            open(os.path.join(tmp_path, f)).read()
            for f in os.listdir(tmp_path))
        for rank in range(np_):
            assert f"rank={rank} size={np_}" in logs

    def test_strategy_sweep(self, tmp_path):
        # reference run-integration-tests.sh sweeps np x strategies
        for strategy in ["STAR", "RING", "BINARY_TREE_STAR"]:
            r = run_kfrun(
                ["-np", "3", "-H", "127.0.0.1:3",
                 "-port-range", "27000-27999",
                 "-strategy", strategy, "-logdir",
                 str(tmp_path / strategy), "-q"],
                "fake_trainer.py",
            )
            assert r.returncode == 0, (strategy, r.stderr[-2000:])

    def test_fail_fast_on_crash(self, tmp_path):
        r = run_kfrun(
            ["-np", "3", "-H", "127.0.0.1:3",
             "-port-range", "28000-28999",
             "-logdir", str(tmp_path), "-q"],
            "fake_crasher.py",
            extra_env={"KF_TIMEOUT_MS": "5000"},
        )
        assert r.returncode != 0


class TestConfigServer:
    def mk_stage(self, np_=2, version=0):
        hl = HostList.parse(f"127.0.0.1:{np_ + 4}")
        return Stage(
            version=version,
            cluster=Cluster(runners=hl.gen_runner_list(),
                            workers=hl.gen_peer_list(np_)),
        )

    def test_put_get_roundtrip(self):
        server = ConfigServer(port=0).start()
        try:
            with pytest.raises(urllib.request.HTTPError):
                fetch_url(server.get_url)
            st = self.mk_stage()
            put_url(server.get_url.replace("/get", "/put"), st.to_json())
            got = Stage.from_json(fetch_url(server.get_url))
            assert got.version == 0
            assert got.cluster == st.cluster
        finally:
            server.stop()

    def test_stale_version_rejected(self):
        server = ConfigServer(port=0).start()
        try:
            put_url(server.get_url.replace("/get", "/put"),
                    self.mk_stage(version=3).to_json())
            with pytest.raises(urllib.request.HTTPError):
                put_url(server.get_url.replace("/get", "/put"),
                        self.mk_stage(version=2).to_json())
        finally:
            server.stop()

    def test_add_remove_clear_reset(self):
        server = ConfigServer(port=0).start()
        base = server.get_url.replace("/get", "")
        try:
            put_url(base + "/put", self.mk_stage(np_=2).to_json())

            def post(path):
                urllib.request.urlopen(
                    urllib.request.Request(base + path, method="POST"),
                    timeout=5).read()

            post("/addworker")
            st = Stage.from_json(fetch_url(base + "/get"))
            assert len(st.cluster.workers) == 3 and st.version == 1
            post("/removeworker")
            st = Stage.from_json(fetch_url(base + "/get"))
            assert len(st.cluster.workers) == 2 and st.version == 2
            post("/clear")
            st = Stage.from_json(fetch_url(base + "/get"))
            assert len(st.cluster.workers) == 0
            post("/reset")
            st = Stage.from_json(fetch_url(base + "/get"))
            assert len(st.cluster.workers) == 2
        finally:
            server.stop()

    def test_invalid_cluster_rejected(self):
        server = ConfigServer(port=0).start()
        try:
            bad = ('{"version": 0, "cluster": {"runners": [], '
                   '"workers": ["127.0.0.1:10000"]}}')
            with pytest.raises(urllib.request.HTTPError):
                put_url(server.get_url.replace("/get", "/put"), bad)
        finally:
            server.stop()


class TestSchedule:
    def test_parse(self):
        assert parse_schedule("3:2,3:4,3:16") == [(3, 2), (3, 4), (3, 16)]

    def test_piecewise(self):
        spec = "3:2,3:4,3:1"
        sizes = [step_based_schedule(spec, s) for s in range(12)]
        assert sizes == [2, 2, 2, 4, 4, 4, 1, 1, 1, 1, 1, 1]

    def test_invalid(self):
        with pytest.raises(ValueError):
            parse_schedule("0:2")
        with pytest.raises(ValueError):
            parse_schedule("")
