"""In-process REST protocol tests (aiohttp test client against the real app),
mirroring the reference's test_server.py/test_dataplane.py strategy."""

import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kserve_tpu import (
    InferInput,
    InferOutput,
    InferRequest,
    InferResponse,
    Model,
    ModelRepository,
)
from kserve_tpu.errors import InferenceError
from kserve_tpu.protocol.model_repository_extension import ModelRepositoryExtension
from kserve_tpu.protocol.openai.dataplane import OpenAIDataPlane
from kserve_tpu.protocol.rest.server import RESTServer

from conftest import async_test


class DummyModel(Model):
    """Echo-style model speaking both v1 dict and v2 InferRequest forms."""

    def __init__(self, name="dummy"):
        super().__init__(name)
        self.ready = True

    async def predict(self, payload, headers=None, response_headers=None):
        if isinstance(payload, InferRequest):
            outputs = []
            for inp in payload.inputs:
                arr = inp.as_numpy()
                out = InferOutput(inp.name.replace("input", "output"), list(arr.shape), inp.datatype)
                if inp.datatype == "BYTES":
                    out.set_data_from_numpy(arr, binary_data=False)
                else:
                    out.set_data_from_numpy(arr * 2, binary_data=inp.raw_data is not None)
                outputs.append(out)
            return InferResponse(payload.id, self.name, outputs)
        instances = payload["instances"]
        return {"predictions": [[v * 2 for v in row] for row in instances]}

    async def explain(self, payload, headers=None):
        return {"explanations": "because"}


class FailingModel(Model):
    def __init__(self):
        super().__init__("fails")
        self.ready = True

    async def predict(self, payload, headers=None, response_headers=None):
        raise InferenceError("boom")


def make_client():
    repo = ModelRepository()
    repo.update(DummyModel())
    repo.update(FailingModel())
    not_ready = DummyModel("notready")
    not_ready.ready = False
    repo.update(not_ready)
    dataplane = OpenAIDataPlane(repo)
    server = RESTServer(dataplane, ModelRepositoryExtension(repo))
    app = server.create_application()
    return TestClient(TestServer(app))


class TestV1:
    @async_test
    async def test_liveness(self):
        async with make_client() as client:
            res = await client.get("/")
            assert res.status == 200
            assert await res.json() == {"status": "alive"}

    @async_test
    async def test_list_models(self):
        async with make_client() as client:
            res = await client.get("/v1/models")
            assert (await res.json())["models"] == ["dummy", "fails", "notready"]

    @async_test
    async def test_model_ready(self):
        async with make_client() as client:
            res = await client.get("/v1/models/dummy")
            assert await res.json() == {"name": "dummy", "ready": True}

    @async_test
    async def test_model_not_found(self):
        async with make_client() as client:
            res = await client.get("/v1/models/ghost")
            assert res.status == 404

    @async_test
    async def test_predict(self):
        async with make_client() as client:
            res = await client.post(
                "/v1/models/dummy:predict", json={"instances": [[1, 2], [3, 4]]}
            )
            assert res.status == 200
            assert (await res.json())["predictions"] == [[2, 4], [6, 8]]

    @async_test
    async def test_predict_not_ready(self):
        async with make_client() as client:
            res = await client.post(
                "/v1/models/notready:predict", json={"instances": [[1]]}
            )
            assert res.status == 503

    @async_test
    async def test_predict_bad_json(self):
        async with make_client() as client:
            res = await client.post(
                "/v1/models/dummy:predict", data=b"{not json", headers={"content-type": "application/json"}
            )
            assert res.status == 400

    @async_test
    async def test_predict_error_500(self):
        async with make_client() as client:
            res = await client.post("/v1/models/fails:predict", json={"instances": [[1]]})
            assert res.status == 500

    @async_test
    async def test_explain(self):
        async with make_client() as client:
            res = await client.post(
                "/v1/models/dummy:explain", json={"instances": [[1]]}
            )
            assert (await res.json())["explanations"] == "because"

    @async_test
    async def test_cloudevent_binary(self):
        async with make_client() as client:
            headers = {
                "ce-specversion": "1.0",
                "ce-source": "test",
                "ce-type": "test.request",
                "ce-id": "123",
                "content-type": "application/json",
            }
            res = await client.post(
                "/v1/models/dummy:predict",
                data=json.dumps({"instances": [[5]]}),
                headers=headers,
            )
            assert res.status == 200
            assert res.headers["ce-source"] == "io.kserve.inference.dummy"
            assert (await res.json())["predictions"] == [[10]]


class TestV2:
    @async_test
    async def test_metadata(self):
        async with make_client() as client:
            res = await client.get("/v2")
            body = await res.json()
            assert body["name"] == "kserve-tpu"
            assert "model_repository_extension" in body["extensions"]

    @async_test
    async def test_health(self):
        async with make_client() as client:
            live = await client.get("/v2/health/live")
            assert (await live.json())["live"] is True

    @async_test
    async def test_model_metadata(self):
        async with make_client() as client:
            res = await client.get("/v2/models/dummy")
            assert (await res.json())["name"] == "dummy"

    @async_test
    async def test_infer_json(self):
        async with make_client() as client:
            body = {
                "id": "1",
                "inputs": [
                    {"name": "input-0", "shape": [2, 2], "datatype": "FP32",
                     "data": [1.0, 2.0, 3.0, 4.0]}
                ],
            }
            res = await client.post("/v2/models/dummy/infer", json=body)
            assert res.status == 200
            out = await res.json()
            assert out["model_name"] == "dummy"
            assert out["outputs"][0]["data"] == [2.0, 4.0, 6.0, 8.0]

    @async_test
    async def test_infer_binary(self):
        async with make_client() as client:
            x = np.arange(4, dtype=np.float32).reshape(2, 2)
            inp = InferInput("input-0", [2, 2], "FP32")
            inp.set_data_from_numpy(x, binary_data=True)
            req = InferRequest(model_name="dummy", infer_inputs=[inp], request_id="bin1")
            body, json_length = req.to_rest()
            res = await client.post(
                "/v2/models/dummy/infer",
                data=body,
                headers={
                    "inference-header-content-length": str(json_length),
                    "content-type": "application/octet-stream",
                },
            )
            assert res.status == 200
            raw = await res.read()
            response = InferResponse.from_bytes(
                raw, int(res.headers["inference-header-content-length"])
            )
            np.testing.assert_array_equal(response.outputs[0].as_numpy(), x * 2)

    @async_test
    async def test_infer_model_not_found(self):
        async with make_client() as client:
            res = await client.post(
                "/v2/models/ghost/infer",
                json={"inputs": [{"name": "a", "shape": [1], "datatype": "INT32", "data": [1]}]},
            )
            assert res.status == 404

    @async_test
    async def test_load_unload(self):
        async with make_client() as client:
            res = await client.post("/v2/repository/models/dummy/load")
            assert (await res.json())["load"] is True
            res = await client.post("/v2/repository/models/dummy/unload")
            assert (await res.json())["unload"] is True
            res = await client.post("/v2/repository/models/dummy/load")
            assert res.status == 404

    @async_test
    async def test_metrics(self):
        async with make_client() as client:
            await client.post(
                "/v1/models/dummy:predict", json={"instances": [[1]]}
            )
            res = await client.get("/metrics")
            text = await res.text()
            assert "request_predict_seconds" in text


class TestPeerPageServer:
    """GET /v1/internal/kv/pages/{digest} (kvstore/peer.py wire contract):
    the read-only, engine-loop-free page server a peer replica fetches
    verified KV prefix pages from.  A GET under /v1/internal is
    structurally exempt from the load shedder (it bounces inference
    POSTs only) — cold peers must be able to warm up from a replica
    that is itself under pressure."""

    @staticmethod
    def make_page_client(pages):
        import types

        repo = ModelRepository()
        model = DummyModel("pager")
        model.engine = types.SimpleNamespace(
            read_peer_page=lambda digest: pages.get(digest))
        repo.update(model)
        dataplane = OpenAIDataPlane(repo)
        server = RESTServer(dataplane, ModelRepositoryExtension(repo))
        app = server.create_application()
        return TestClient(TestServer(app))

    @async_test
    async def test_resident_page_served_in_verifiable_wire_form(self):
        from kserve_tpu.kvstore import PAGE_ROUTE, decode_page, encode_page

        digest = b"\xab" * 16
        wire = encode_page(digest, b"raw persisted page file bytes")
        async with self.make_page_client({digest: wire}) as client:
            resp = await client.get(f"{PAGE_ROUTE}/{digest.hex()}")
            assert resp.status == 200
            assert resp.content_type == "application/octet-stream"
            body = await resp.read()
            assert body == wire
            # the fetcher re-verifies before adoption; the served bytes
            # must survive that check as-is
            assert decode_page(body, digest) == b"raw persisted page file bytes"

    @async_test
    async def test_missing_page_is_404(self):
        from kserve_tpu.kvstore import PAGE_ROUTE

        async with self.make_page_client({}) as client:
            resp = await client.get(f"{PAGE_ROUTE}/{'00' * 16}")
            assert resp.status == 404

    @async_test
    async def test_undecodable_digest_is_404_not_500(self):
        from kserve_tpu.kvstore import PAGE_ROUTE

        async with self.make_page_client({}) as client:
            resp = await client.get(f"{PAGE_ROUTE}/not-hex-at-all")
            assert resp.status == 404

    @async_test
    async def test_engineless_models_are_skipped(self):
        from kserve_tpu.kvstore import PAGE_ROUTE

        async with make_client() as client:  # models without engines
            resp = await client.get(f"{PAGE_ROUTE}/{'11' * 16}")
            assert resp.status == 404
