"""Multi-host bootstrap: jax.distributed.initialize from the env the LLMISVC
controller injects (VERDICT #5)."""

import os
import socket
import subprocess
import sys

import pytest

from kserve_tpu.utils.distributed import infer_process_id, maybe_initialize_distributed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestEnvParsing:
    def test_noop_without_env(self):
        assert maybe_initialize_distributed(env={}) is False

    def test_single_host_skips(self):
        assert (
            maybe_initialize_distributed(
                env={"COORDINATOR_ADDRESS": "x:1", "NUM_PROCESSES": "1"}
            )
            is False
        )

    def test_missing_rank_is_loud(self, monkeypatch):
        monkeypatch.setenv("HOSTNAME", "not-a-statefulset-pod")
        monkeypatch.delenv("PROCESS_ID", raising=False)
        monkeypatch.delenv("JOB_COMPLETION_INDEX", raising=False)
        with pytest.raises(RuntimeError, match="rank"):
            maybe_initialize_distributed(
                env={"COORDINATOR_ADDRESS": "x:1", "NUM_PROCESSES": "4"}
            )

    def test_rank_from_statefulset_hostname(self, monkeypatch):
        monkeypatch.delenv("PROCESS_ID", raising=False)
        monkeypatch.delenv("JOB_COMPLETION_INDEX", raising=False)
        monkeypatch.setenv("HOSTNAME", "myllm-kserve-3")
        assert infer_process_id() == 3

    def test_rank_env_beats_hostname(self, monkeypatch):
        monkeypatch.setenv("HOSTNAME", "myllm-kserve-3")
        monkeypatch.setenv("PROCESS_ID", "1")
        assert infer_process_id() == 1


_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from kserve_tpu.utils.distributed import maybe_initialize_distributed
assert maybe_initialize_distributed() is True
assert jax.process_count() == 2, jax.process_count()
assert jax.process_index() == int(os.environ["PROCESS_ID"])
# a cross-host collective actually runs
import jax.numpy as jnp
from jax.experimental import multihost_utils
total = multihost_utils.process_allgather(jnp.asarray([jax.process_index()]))
assert sorted(int(x) for x in total.ravel()) == [0, 1], total
print("WORKER_OK", jax.process_index())
"""


@pytest.mark.slow
class TestLoopbackCoordinator:
    def test_two_process_initialize_and_allgather(self, tmp_path):
        """Two local processes join via a loopback coordinator exactly the
        way two slice hosts would via the peer Service."""
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        script = tmp_path / "worker.py"
        script.write_text(_WORKER.format(repo=REPO))
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update(
                COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                NUM_PROCESSES="2",
                PROCESS_ID=str(rank),
                PYTHONPATH=REPO,
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                )
            )
        for rank, proc in enumerate(procs):
            out, _ = proc.communicate(timeout=120)
            text = out.decode(errors="replace")
            assert proc.returncode == 0, f"rank {rank} failed:\n{text[-2000:]}"
            assert f"WORKER_OK {rank}" in text


class TestControllerMultiHost:
    def _reconcile(self, tp):
        from kserve_tpu.controlplane.crds import LLMInferenceService
        from kserve_tpu.controlplane.llmisvc import LLMISVCReconciler

        llm = LLMInferenceService.model_validate(
            {
                "apiVersion": "serving.kserve.io/v1alpha2",
                "kind": "LLMInferenceService",
                "metadata": {"name": "big", "namespace": "prod"},
                "spec": {
                    "model": {"uri": "hf://meta/llama", "name": "llm"},
                    "workload": {"parallelism": {"tensor": tp}},
                },
            }
        )
        return LLMISVCReconciler().reconcile(llm)

    def test_multihost_workload_is_statefulset_with_rankable_pods(self):
        # tp=8 on v5e (4 chips/host) -> 2 hosts
        objects, _ = self._reconcile(tp=8)
        sts = [o for o in objects if o["kind"] == "StatefulSet"]
        assert len(sts) == 1
        spec = sts[0]["spec"]
        assert spec["serviceName"] == "big-kserve-peers"
        assert spec["podManagementPolicy"] == "Parallel"
        env = {
            e["name"]: e["value"]
            for e in spec["template"]["spec"]["containers"][0]["env"]
        }
        assert env["COORDINATOR_ADDRESS"] == "big-kserve-0.big-kserve-peers.prod:8476"
        assert env["NUM_PROCESSES"] == "2"
        # the env round-trips into the runtime's bootstrap: a pod named by
        # the StatefulSet ordinal resolves its rank and would initialize
        from kserve_tpu.utils import distributed as dist

        old = os.environ.get("HOSTNAME")
        os.environ["HOSTNAME"] = "big-kserve-1"
        try:
            assert dist.infer_process_id() == 1
        finally:
            if old is None:
                os.environ.pop("HOSTNAME", None)
            else:
                os.environ["HOSTNAME"] = old
        # headless peer service exists for the coordinator DNS name
        svcs = [
            o for o in objects
            if o["kind"] == "Service" and o["metadata"]["name"] == "big-kserve-peers"
        ]
        assert len(svcs) == 1 and svcs[0]["spec"]["clusterIP"] == "None"

    def test_single_host_stays_deployment(self):
        objects, _ = self._reconcile(tp=2)
        kinds = [o["kind"] for o in objects]
        assert "StatefulSet" not in kinds
        assert "Deployment" in kinds
