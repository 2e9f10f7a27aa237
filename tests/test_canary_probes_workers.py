"""VERDICT #9: canary traffic split, synthesized-pod probes, multiprocess
REST workers."""

import os
import subprocess
import sys
import time

import pytest

from kserve_tpu.controlplane.cluster import ControllerManager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def isvc(uri="gs://b/m", canary=None):
    spec = {"predictor": {"model": {
        "modelFormat": {"name": "sklearn"}, "storageUri": uri}}}
    if canary is not None:
        spec["predictor"]["canaryTrafficPercent"] = canary
    return {
        "apiVersion": "serving.kserve.io/v1beta1",
        "kind": "InferenceService",
        "metadata": {"name": "c", "namespace": "default"},
        "spec": spec,
    }


class TestCanary:
    def test_first_rollout_then_canary_then_promote(self):
        mgr = ControllerManager()
        # 1. plain rollout: stable deployment + unweighted route
        mgr.apply(isvc(uri="gs://b/v1"))
        assert mgr.cluster.get("Deployment", "c-predictor") is not None
        route = mgr.cluster.get("HTTPRoute", "c")
        refs = route["spec"]["rules"][-1]["backendRefs"]
        assert refs == [{"name": "c-predictor", "port": 80}]

        # 2. canary rollout: canary deployment joins, weighted route
        mgr.apply(isvc(uri="gs://b/v2", canary=20))
        stable = mgr.cluster.get("Deployment", "c-predictor")
        canary = mgr.cluster.get("Deployment", "c-predictor-canary")
        assert stable is not None and canary is not None
        # the canary runs the NEW model; the stable keeps the old one
        def model_uri(dep):
            init = dep["spec"]["template"]["spec"]["initContainers"][0]
            return init["args"][0]
        assert model_uri(canary) == "gs://b/v2"
        assert model_uri(stable) == "gs://b/v1"
        refs = mgr.cluster.get("HTTPRoute", "c")["spec"]["rules"][-1]["backendRefs"]
        assert refs == [
            {"name": "c-predictor", "port": 80, "weight": 80},
            {"name": "c-predictor-canary", "port": 80, "weight": 20},
        ]
        isvc_obj = mgr.cluster.get("InferenceService", "c")
        assert isvc_obj["status"]["canary"] == {"trafficPercent": 20, "hasStable": True}

        # 3. promote: canary field removed -> new spec becomes stable, the
        # canary deployment is garbage-collected
        mgr.apply(isvc(uri="gs://b/v2"))
        mgr.reconcile_all()
        assert model_uri(mgr.cluster.get("Deployment", "c-predictor")) == "gs://b/v2"
        assert mgr.cluster.get("Deployment", "c-predictor-canary") is None
        refs = mgr.cluster.get("HTTPRoute", "c")["spec"]["rules"][-1]["backendRefs"]
        assert refs == [{"name": "c-predictor", "port": 80}]

    def test_canary_without_stable_gets_all_traffic(self):
        mgr = ControllerManager()
        mgr.apply(isvc(uri="gs://b/v1", canary=10))
        refs = mgr.cluster.get("HTTPRoute", "c")["spec"]["rules"][-1]["backendRefs"]
        assert refs == [{"name": "c-predictor-canary", "port": 80, "weight": 100}]


class TestProbes:
    def test_isvc_deployment_has_probes(self):
        mgr = ControllerManager()
        mgr.apply(isvc())
        container = mgr.cluster.get("Deployment", "c-predictor")[
            "spec"]["template"]["spec"]["containers"][0]
        assert container["readinessProbe"]["httpGet"]["path"] == "/v2/health/ready"
        assert container["livenessProbe"]["httpGet"]["path"] == "/v2/health/live"

    def test_llmisvc_workload_has_probes(self):
        mgr = ControllerManager()
        mgr.apply({
            "apiVersion": "serving.kserve.io/v1alpha2",
            "kind": "LLMInferenceService",
            "metadata": {"name": "l", "namespace": "default"},
            "spec": {"model": {"uri": "hf://org/m", "name": "llm"}},
        })
        container = mgr.cluster.get("Deployment", "l-kserve")[
            "spec"]["template"]["spec"]["containers"][0]
        assert "readinessProbe" in container and "livenessProbe" in container


_WORKER_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from kserve_tpu.model import Model
from kserve_tpu.model_server import ModelServer

class Echo(Model):
    def load(self):
        self.ready = True
        return True
    async def predict(self, payload, headers=None, response_headers=None):
        return {{"predictions": [os.getpid()]}}

m = Echo("echo")
m.load()
ModelServer(http_port={port}, enable_grpc=False, workers=2).start([m])
"""


@pytest.mark.slow
class TestMultiprocessWorkers:
    def test_two_workers_share_the_port(self, tmp_path):
        import httpx

        port = 19310
        script = tmp_path / "serve.py"
        script.write_text(_WORKER_SCRIPT.format(repo=REPO, port=port))
        proc = subprocess.Popen(
            [sys.executable, str(script)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.time() + 60
            pids = set()
            while time.time() < deadline:
                try:
                    r = httpx.post(
                        f"http://127.0.0.1:{port}/v1/models/echo:predict",
                        json={"instances": [1]}, timeout=3,
                    )
                    if r.status_code == 200:
                        pids.add(r.json()["predictions"][0])
                        if len(pids) >= 2:
                            break
                # connection errors while the subprocess boots are the
                # retry condition; the sleep is the backoff (sync test)
                except Exception:  # jaxlint: disable=swallowed-exception
                    time.sleep(0.5)  # jaxlint: disable=blocking-async
                    continue
                # brief gap between fresh connections (sync test thread)
                time.sleep(0.05)  # jaxlint: disable=blocking-async
            assert pids, "server never came up"
            # kernel load-balances connections across SO_REUSEPORT sockets;
            # with enough fresh connections both workers must appear
            assert len(pids) >= 2, f"only worker pids {pids} served"
        finally:
            proc.terminate()
            proc.wait(timeout=10)

    def test_engine_models_reject_workers(self):
        from kserve_tpu.model_server import ModelServer
        from kserve_tpu.runtimes.generative_server import JAXGenerativeModel

        model = JAXGenerativeModel("llm", model_config=None, random_weights=True)
        with pytest.raises(ValueError, match="workers"):
            ModelServer(workers=2, enable_grpc=False)._start_multiprocess([model])
