"""jaxlint (kserve_tpu.analysis) rule tests.

Each rule gets three fixtures: a known-bad snippet it must flag, a
known-good snippet it must stay quiet on, and the bad snippet with a
``# jaxlint: disable=<rule>`` comment it must respect.  The final tests
assert the real tree lints clean and that the suppression budget holds.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from kserve_tpu.analysis import all_rules, lint_paths, lint_source

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO_ROOT, "kserve_tpu")


def rules_of(src, select=None):
    findings = lint_source(textwrap.dedent(src), path="fixture.py", select=select)
    return [f.rule for f in findings]


# ---------------------------------------------------------------- registry

def test_at_least_six_rules_registered():
    assert len(all_rules()) >= 6


def test_syntax_error_is_reported_not_raised():
    assert rules_of("def broken(:\n") == ["syntax-error"]


# ------------------------------------------------- donated-buffer-reuse

BAD_DONATION = """
    import jax

    decode = jax.jit(_decode, donate_argnums=(0,))

    def step(kv_pages, tokens):
        out, kv_new = decode(kv_pages, tokens)
        return kv_pages.sum()  # read after donation
"""

GOOD_DONATION = """
    import jax

    decode = jax.jit(_decode, donate_argnums=(0,))

    def step(kv_pages, tokens):
        out, kv_pages = decode(kv_pages, tokens)  # rebind: correct idiom
        return kv_pages.sum()
"""


def test_donation_fires_on_read_after_donate():
    assert "donated-buffer-reuse" in rules_of(BAD_DONATION)


def test_donation_quiet_on_rebind():
    assert "donated-buffer-reuse" not in rules_of(GOOD_DONATION)


def test_donation_argnames_form():
    src = """
        import jax
        f = jax.jit(g, donate_argnames=("cache",))
        def step(cache):
            y = f(cache=cache)
            return cache
    """
    assert "donated-buffer-reuse" in rules_of(src)


def test_donation_suppressed():
    src = BAD_DONATION.replace(
        "return kv_pages.sum()  # read after donation",
        "return kv_pages.sum()  # jaxlint: disable=donated-buffer-reuse",
    )
    assert "donated-buffer-reuse" not in rules_of(src)


def test_donation_branch_does_not_poison_after():
    src = """
        import jax
        f = jax.jit(g, donate_argnums=(0,))
        def step(kv, flag):
            if flag:
                y = f(kv)
            kv = make_new_kv()
            return kv.sum()
    """
    assert "donated-buffer-reuse" not in rules_of(src)


# ---------------------------------------------------- recompile-hazard

BAD_RECOMPILE = """
    import jax

    @jax.jit
    def step(x):
        if bool(x):  # concretizes a tracer
            return x
        return x + 1
"""

GOOD_RECOMPILE = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        n = int(x.shape[0])  # static: fine
        return jnp.where(x > 0, x, -x) + n
"""


def test_recompile_fires_on_bool_of_tracer():
    assert "recompile-hazard" in rules_of(BAD_RECOMPILE)


def test_recompile_fires_on_item():
    src = """
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()
    """
    assert "recompile-hazard" in rules_of(src)


def test_recompile_quiet_on_static_shapes():
    assert "recompile-hazard" not in rules_of(GOOD_RECOMPILE)


def test_recompile_quiet_outside_jit():
    src = """
        def host_fn(x):
            return bool(x)
    """
    assert "recompile-hazard" not in rules_of(src)


def test_recompile_detects_factory_idiom():
    # the engine/compiled.py shape: jax.jit(_make_decode(...)) traces the
    # function the factory returns
    src = """
        import jax

        def _make_decode(flag):
            def fn(x):
                return float(x)
            return fn

        decode = jax.jit(_make_decode(True), donate_argnums=(0,))
    """
    assert "recompile-hazard" in rules_of(src)


def test_recompile_suppressed():
    src = BAD_RECOMPILE.replace(
        "if bool(x):  # concretizes a tracer",
        "if bool(x):  # jaxlint: disable=recompile-hazard",
    )
    assert "recompile-hazard" not in rules_of(src)


# ------------------------------------------------------ blocking-async

BAD_BLOCKING = """
    import time

    async def poll_backend(url):
        time.sleep(0.5)  # stalls the event loop
        return url
"""

GOOD_BLOCKING = """
    import asyncio

    async def poll_backend(url):
        await asyncio.sleep(0.5)
        return url
"""


def test_blocking_fires_on_sleep_in_async():
    assert "blocking-async" in rules_of(BAD_BLOCKING)


def test_blocking_fires_on_sync_http_in_async():
    src = """
        import requests

        async def fetch(url):
            return requests.get(url)
    """
    assert "blocking-async" in rules_of(src)


def test_blocking_fires_on_sync_sleep_in_server_code():
    src = """
        import time

        def watch_loop(stop):
            while not stop.is_set():
                time.sleep(0.5)
    """
    assert "blocking-async" in rules_of(src)


def test_blocking_quiet_on_asyncio_sleep():
    assert "blocking-async" not in rules_of(GOOD_BLOCKING)


def test_blocking_quiet_on_event_wait():
    src = """
        def watch_loop(stop):
            while not stop.is_set():
                stop.wait(0.5)
    """
    assert "blocking-async" not in rules_of(src)


def test_blocking_exempts_nested_sync_helper():
    # a thunk defined inside an async def and handed to run_in_executor
    # legitimately blocks — in the executor thread, not on the loop
    src = """
        import asyncio, time

        async def load(path):
            def _work():
                time.sleep(1.0)
                return path
            return await asyncio.get_event_loop().run_in_executor(None, _work)
    """
    # an executor-destined thunk blocks in a worker thread, not on the
    # loop: exempt from both the async-context check and the sleep sweep
    assert "blocking-async" not in rules_of(src)


def test_blocking_suppressed():
    src = BAD_BLOCKING.replace(
        "time.sleep(0.5)  # stalls the event loop",
        "time.sleep(0.5)  # jaxlint: disable=blocking-async",
    )
    assert "blocking-async" not in rules_of(src)


# ---------------------------------------------------------- pspec-axis

BAD_PSPEC = """
    from jax.sharding import PartitionSpec as P

    spec = P("rows", None)  # not a mesh axis
"""

GOOD_PSPEC = """
    from jax.sharding import PartitionSpec as P

    spec = P("model", None)
    spec2 = P(None, ("data", "seq"))
"""


def test_pspec_fires_on_unknown_axis():
    assert "pspec-axis" in rules_of(BAD_PSPEC)


def test_pspec_quiet_on_vocabulary_axes():
    assert "pspec-axis" not in rules_of(GOOD_PSPEC)


def test_pspec_quiet_on_named_constants():
    src = """
        import jax
        from . import sharding as shd

        spec = jax.sharding.PartitionSpec(None, shd.SEQ_AXIS)
    """
    assert "pspec-axis" not in rules_of(src)


def test_pspec_ignores_unrelated_P():
    # P that is not jax.sharding.PartitionSpec must not be checked
    src = """
        def P(*args):
            return args

        x = P("rows", "whatever")
    """
    assert "pspec-axis" not in rules_of(src)


def test_pspec_suppressed():
    src = BAD_PSPEC.replace(
        'spec = P("rows", None)  # not a mesh axis',
        'spec = P("rows", None)  # jaxlint: disable=pspec-axis',
    )
    assert "pspec-axis" not in rules_of(src)


# ------------------------------------------------- swallowed-exception

BAD_EXCEPT = """
    def load(path):
        try:
            return open(path).read()
        except Exception:
            return None
"""

GOOD_EXCEPT = """
    from kserve_tpu.logging import logger

    def load(path):
        try:
            return open(path).read()
        except Exception:
            logger.warning("load of %s failed", path, exc_info=True)
            return None
"""


def test_except_fires_on_silent_broad_catch():
    assert "swallowed-exception" in rules_of(BAD_EXCEPT)


def test_except_fires_on_bare_except():
    src = """
        def f():
            try:
                g()
            except:
                pass
    """
    assert "swallowed-exception" in rules_of(src)


def test_except_quiet_when_logged():
    assert "swallowed-exception" not in rules_of(GOOD_EXCEPT)


def test_except_quiet_when_reraised_typed():
    src = """
        from kserve_tpu.errors import InferenceError

        def f():
            try:
                g()
            except Exception as e:
                raise InferenceError(str(e)) from e
    """
    assert "swallowed-exception" not in rules_of(src)


def test_except_quiet_on_narrow_type():
    src = """
        def f():
            try:
                g()
            except ValueError:
                return None
    """
    assert "swallowed-exception" not in rules_of(src)


def test_except_quiet_on_future_relay():
    src = """
        def f(fut):
            try:
                g()
            except Exception as e:
                fut.set_exception(e)
    """
    assert "swallowed-exception" not in rules_of(src)


def test_except_suppressed():
    src = BAD_EXCEPT.replace(
        "except Exception:",
        "except Exception:  # jaxlint: disable=swallowed-exception",
    )
    assert "swallowed-exception" not in rules_of(src)


# ------------------------------------------------------------ host-sync

BAD_HOSTSYNC = """
    import jax
    import numpy as np

    @jax.jit
    def decode_step(x):
        return np.asarray(x)  # device-to-host per step
"""

GOOD_HOSTSYNC = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def decode_step(x):
        return jnp.asarray(x)
"""


def test_hostsync_fires_on_np_asarray_in_jit():
    assert "host-sync" in rules_of(BAD_HOSTSYNC)


def test_hostsync_fires_on_tolist_in_jit():
    src = """
        import jax

        @jax.jit
        def decode_step(x):
            return x.tolist()
    """
    assert "host-sync" in rules_of(src)


def test_hostsync_quiet_on_jnp():
    assert "host-sync" not in rules_of(GOOD_HOSTSYNC)


def test_hostsync_quiet_outside_jit():
    src = """
        import numpy as np

        def postprocess(x):
            return np.asarray(x).tolist()
    """
    assert "host-sync" not in rules_of(src)


def test_hostsync_suppressed():
    src = BAD_HOSTSYNC.replace(
        "return np.asarray(x)  # device-to-host per step",
        "return np.asarray(x)  # jaxlint: disable=host-sync",
    )
    assert "host-sync" not in rules_of(src)


# ------------------------------------------------------- suppressions

def test_file_level_suppression():
    src = """
        # jaxlint: disable-file=swallowed-exception
        def f():
            try:
                g()
            except Exception:
                return None
    """
    assert "swallowed-exception" not in rules_of(src)


def test_disable_all():
    src = BAD_EXCEPT.replace(
        "except Exception:",
        "except Exception:  # jaxlint: disable=all",
    )
    assert rules_of(src) == []


def test_unrelated_rule_suppression_does_not_hide():
    src = BAD_EXCEPT.replace(
        "except Exception:",
        "except Exception:  # jaxlint: disable=pspec-axis",
    )
    assert "swallowed-exception" in rules_of(src)


# ------------------------------------------------------- the real tree

def test_kserve_tpu_tree_lints_clean():
    findings = lint_paths([PKG_DIR])
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------- ragged-metadata-host-sync

BAD_RAGGED = """
    import jax

    @jax.jit
    def mixed_step(q_tokens, q_start, q_len, kv_start):
        n = int(q_len[0])  # host sync on packing metadata
        first = q_start.item()
        return q_tokens[first:first + n]
"""

GOOD_RAGGED = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def mixed_step(q_tokens, q_start, q_len, kv_start):
        idx = jnp.arange(q_tokens.shape[0])
        member = (idx[None] >= q_start[:, None]) & (
            idx[None] < (q_start + q_len)[:, None])
        return jnp.where(member.any(0), q_tokens, 0)
"""

GOOD_RAGGED_HOST = """
    def plan_ragged(meta, q_start, q_len):
        # host-side planning over numpy arrays is the intended place for
        # scalar reads — only TRACED code is in scope for the rule
        return int(q_len[0]) + q_start.item()
"""


def test_ragged_host_sync_fires_on_item_and_int():
    rules = rules_of(BAD_RAGGED)
    assert rules.count("ragged-metadata-host-sync") == 2


def test_ragged_host_sync_quiet_on_device_derivation():
    assert "ragged-metadata-host-sync" not in rules_of(GOOD_RAGGED)


def test_ragged_host_sync_quiet_outside_traced_code():
    assert "ragged-metadata-host-sync" not in rules_of(GOOD_RAGGED_HOST)


def test_ragged_host_sync_attribute_and_subscript_bases():
    src = """
        import jax

        @jax.jit
        def step(meta):
            a = meta.kv_start.item()
            b = int(meta.block_seq[3])
            return a + b
    """
    assert rules_of(src).count("ragged-metadata-host-sync") == 2


def test_ragged_host_sync_suppressed():
    src = BAD_RAGGED.replace(
        "n = int(q_len[0])  # host sync on packing metadata",
        "n = int(q_len[0])  # jaxlint: disable=ragged-metadata-host-sync"
    ).replace(
        "first = q_start.item()",
        "first = q_start.item()  # jaxlint: disable=ragged-metadata-host-sync"
    )
    assert "ragged-metadata-host-sync" not in rules_of(src)


# ------------------------------------------- spec-accept-host-sync

BAD_SPEC = """
    import jax

    @jax.jit
    def verify_round(sampled, drafts, acc, n_emit, draft_table):
        # per-round host syncs on acceptance metadata
        k = int(acc[0])
        m = n_emit.item()
        return sampled[:k], m
"""

GOOD_SPEC_DEVICE = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def verify_round(sampled, drafts, acc):
        # acceptance stays vectorized on device
        n_emit = jnp.where(acc >= 0, acc + 1, 0)
        return jnp.take_along_axis(sampled, acc[:, None], axis=1), n_emit
"""

GOOD_SPEC_HOST = """
    def route_dense(plan, toks_np, n_np):
        # HOST routing over the once-per-dispatch fetched numpy outputs
        # is the intended place for scalar reads
        return int(n_np[0, 0]) + int(toks_np[0, 0, 0])
"""


def test_spec_accept_host_sync_fires_on_item_and_int():
    assert rules_of(BAD_SPEC).count("spec-accept-host-sync") == 2


def test_spec_accept_host_sync_quiet_on_device_acceptance():
    assert "spec-accept-host-sync" not in rules_of(GOOD_SPEC_DEVICE)


def test_spec_accept_host_sync_quiet_outside_traced_code():
    assert "spec-accept-host-sync" not in rules_of(GOOD_SPEC_HOST)


def test_spec_accept_host_sync_draft_table_attribute_base():
    src = """
        import jax

        @jax.jit
        def step(state):
            return int(state.draft_table[0, 0])
    """
    assert rules_of(src).count("spec-accept-host-sync") == 1


def test_spec_accept_host_sync_suppressed():
    src = BAD_SPEC.replace(
        "k = int(acc[0])",
        "k = int(acc[0])  # jaxlint: disable=spec-accept-host-sync"
    ).replace(
        "m = n_emit.item()",
        "m = n_emit.item()  # jaxlint: disable=spec-accept-host-sync"
    )
    assert "spec-accept-host-sync" not in rules_of(src)


# ------------------------------------------- aot-cache-key-drift

BAD_AOTKEY = """
    AOT_KEY_ENGINE_FIELDS = ("page_size", "steps_per_sync")

    def build_compiled(model_config, engine_config, mesh, aot_cache=None):
        cfg = engine_config
        steps = cfg.steps_per_sync          # covered
        pages = cfg.page_size               # covered
        fancy = cfg.new_kernel_flag         # NOT in the digest: drift
        quant = getattr(cfg, "act_quant", None)  # getattr spelling: drift
        return steps + pages
"""

GOOD_AOTKEY = """
    AOT_KEY_ENGINE_FIELDS = ("page_size", "steps_per_sync", "kv_quant")

    def build_compiled(model_config, engine_config, mesh, aot_cache=None):
        cfg = engine_config
        quant = getattr(cfg, "kv_quant", None)
        return cfg.page_size * cfg.steps_per_sync
"""

GOOD_AOTKEY_ELSEWHERE = """
    # config reads OUTSIDE build_compiled are not compiled-program
    # construction: the engine reads scheduling knobs freely
    def plan_batch(engine_config):
        return engine_config.queue_policy
"""


def test_aotkey_fires_on_uncovered_reads():
    rules = rules_of(BAD_AOTKEY)
    assert rules.count("aot-cache-key-drift") == 2


def test_aotkey_quiet_when_fields_covered():
    assert "aot-cache-key-drift" not in rules_of(GOOD_AOTKEY)


def test_aotkey_quiet_outside_build_compiled():
    assert "aot-cache-key-drift" not in rules_of(GOOD_AOTKEY_ELSEWHERE)


def test_aotkey_fires_when_no_field_list_resolvable():
    src = """
        def build_compiled(model_config, engine_config, mesh):
            return engine_config.page_size
    """
    assert "aot-cache-key-drift" in rules_of(src)


def test_aotkey_resolves_sibling_aot_cache_module(tmp_path):
    """The real tree layout: the digest list lives in aot_cache.py next
    to compiled.py — the rule must read it from there."""
    (tmp_path / "aot_cache.py").write_text(
        'AOT_KEY_ENGINE_FIELDS = ("page_size",)\n')
    (tmp_path / "compiled.py").write_text(textwrap.dedent("""
        def build_compiled(model_config, engine_config, mesh):
            ok = engine_config.page_size
            bad = engine_config.brand_new_flag
            return ok
    """))
    findings = lint_paths([str(tmp_path / "compiled.py")])
    hits = [f for f in findings if f.rule == "aot-cache-key-drift"]
    assert len(hits) == 1
    assert "brand_new_flag" in hits[0].message


def test_aotkey_suppressed():
    src = BAD_AOTKEY.replace(
        "fancy = cfg.new_kernel_flag         # NOT in the digest: drift",
        "fancy = cfg.new_kernel_flag  # jaxlint: disable=aot-cache-key-drift",
    ).replace(
        'quant = getattr(cfg, "act_quant", None)  # getattr spelling: drift',
        'quant = getattr(cfg, "act_quant", None)  # jaxlint: disable=aot-cache-key-drift',
    )
    assert "aot-cache-key-drift" not in rules_of(src)


@pytest.mark.parametrize("module", ["compiled.py", "shapes.py"])
def test_aotkey_real_tree_digest_covers_build_compiled(module):
    """The production files stay in lockstep: engine/compiled.py, and
    engine/shapes.py whose DispatchShapes.of it hands the config to, lint
    clean under the rule against engine/aot_cache.py's field list."""
    path = os.path.join(PKG_DIR, "engine", module)
    findings = lint_paths([path], select=["aot-cache-key-drift"])
    assert findings == []


def test_aotkey_audits_dispatch_shapes_of():
    """program_defs passes the config whole to DispatchShapes.of: a read
    there is a read during compiled-program construction."""
    src = """
        AOT_KEY_ENGINE_FIELDS = ("use_pallas",)

        class DispatchShapes:
            @classmethod
            def of(cls, model_config, engine_config, backend):
                ok = engine_config.use_pallas
                return cls(engine_config.queue_policy)

            def width(self, cfg):
                return cfg.anything
    """
    findings = [f for f in lint_source(textwrap.dedent(src), path="fixture.py")
                if f.rule == "aot-cache-key-drift"]
    assert len(findings) == 1 and "queue_policy" in findings[0].message


# ------------------------------------------- pagein-host-sync

BAD_PAGEIN = """
    import jax

    async def _page_in(self, req, run):
        payloads = self._fetcher.fetch(read, 30.0)  # sync fetch: serializes
        out = self._inject_fn(self.kv_pages, payloads, ids)
        out.block_until_ready()  # waits on the upload
        n = out[0].item()  # reads the inject result
        return n
"""

GOOD_PAGEIN = """
    import jax.numpy as jnp

    async def _page_in(self, req, run):
        # blocking work rides the fetch worker; the upload is
        # dispatch-only and nothing reads its result
        payloads = await self._fetcher.fetch_async(read, 30.0)
        self.kv_pages = self._inject_fn(
            self.kv_pages, jnp.asarray(payloads), jnp.asarray(ids))
        self._prefix_cache.adopt(entries)
"""

GOOD_NON_PAGEIN = """
    def spill(self, slot):
        # the preemption spill is synchronous BY DESIGN (nothing overlaps
        # a preemption) — only page-in-named functions are in scope
        return self._fetch(self.kv_pages)
"""


def test_pagein_host_sync_fires_on_sync_fetch_and_blocking_reads():
    rules = rules_of(BAD_PAGEIN)
    assert rules.count("pagein-host-sync") == 3


def test_pagein_host_sync_quiet_on_async_dispatch_only_path():
    assert "pagein-host-sync" not in rules_of(GOOD_PAGEIN)


def test_pagein_host_sync_quiet_outside_pagein_functions():
    assert "pagein-host-sync" not in rules_of(GOOD_NON_PAGEIN)


def test_pagein_host_sync_covers_maybe_page_in_spelling():
    src = """
        def _maybe_page_in(self, req, keys):
            run = self._kv_store.longest_prefix_run(keys)
            return jax.device_get(run)
    """
    assert rules_of(src).count("pagein-host-sync") == 1


def test_pagein_host_sync_covers_peer_fetch_family():
    # ISSUE 19: kvstore/peer.py's verified cross-replica leg is in
    # scope — a wall-clock sleep or sync fetch inside fetch_page/
    # fetch_from blocks the event loop the breaker + deadline math
    # assumes is free-running
    src = """
        import time

        async def fetch_page(self, digest, peers):
            time.sleep(0.05)  # backoff on the thread, not the clock
            return self._transport.fetch(digest)
    """
    rules = rules_of(src)
    assert rules.count("pagein-host-sync") == 2


def test_pagein_host_sync_quiet_on_clock_injected_peer_fetch():
    src = """
        async def fetch_from(self, peer_url, digest):
            await self.clock.sleep(delay)  # injected clock: simulable
            resp = await self._client.get(self._url(peer_url, digest))
            return decode_page(resp.content, digest)
    """
    assert "pagein-host-sync" not in rules_of(src)


def test_pagein_host_sync_suppressed():
    src = BAD_PAGEIN.replace(
        "payloads = self._fetcher.fetch(read, 30.0)  # sync fetch: serializes",
        "payloads = self._fetcher.fetch(read, 30.0)  "
        "# jaxlint: disable=pagein-host-sync"
    ).replace(
        "out.block_until_ready()  # waits on the upload",
        "out.block_until_ready()  # jaxlint: disable=pagein-host-sync"
    ).replace(
        "n = out[0].item()  # reads the inject result",
        "n = out[0].item()  # jaxlint: disable=pagein-host-sync"
    )
    assert "pagein-host-sync" not in rules_of(src)


# ---------------------------------------------------------- task-leak

BAD_TASK_LEAK = """
    import asyncio

    async def serve(self):
        asyncio.create_task(self._poll_loop())  # dropped: GC can kill it
        asyncio.get_running_loop().create_task(self._watch())  # dropped
        loop = asyncio.get_event_loop()
        loop.create_task(self._churn())  # dropped
"""

GOOD_TASK_LEAK = """
    import asyncio

    async def serve(self):
        self._poll_task = asyncio.create_task(self._poll_loop())
        self._tasks.append(asyncio.create_task(self._client(req)))
        task = asyncio.get_running_loop().create_task(self._watch())
        task.add_done_callback(self._tasks.discard)
        await asyncio.create_task(self._once())  # awaited: held by await
        return asyncio.create_task(self._run())  # returned to the caller
"""


def test_task_leak_fires_on_dropped_create_task():
    assert rules_of(BAD_TASK_LEAK).count("task-leak") == 3


def test_task_leak_quiet_when_reference_kept():
    assert "task-leak" not in rules_of(GOOD_TASK_LEAK)


def test_task_leak_quiet_on_other_expression_statements():
    src = """
        import asyncio

        async def serve(self):
            self._wake.set()
            await asyncio.sleep(0)
    """
    assert "task-leak" not in rules_of(src)


def test_task_leak_suppressed():
    src = BAD_TASK_LEAK.replace(
        "asyncio.create_task(self._poll_loop())  # dropped: GC can kill it",
        "asyncio.create_task(self._poll_loop())  "
        "# jaxlint: disable=task-leak — fire-and-forget by design",
    ).replace(
        "asyncio.get_running_loop().create_task(self._watch())  # dropped",
        "asyncio.get_running_loop().create_task(self._watch())  "
        "# jaxlint: disable=task-leak — fire-and-forget by design",
    ).replace(
        "loop.create_task(self._churn())  # dropped",
        "loop.create_task(self._churn())  "
        "# jaxlint: disable=task-leak — fire-and-forget by design",
    )
    assert "task-leak" not in rules_of(src)


def test_suppression_budget():
    """≤ 10 jaxlint suppression comments across kserve_tpu/, each carrying
    justification prose in the suppressing comment or the line above."""
    pat = re.compile(r"#\s*jaxlint:\s*disable")
    count = 0
    for root, dirs, files in os.walk(PKG_DIR):
        # the analysis package documents the directive syntax in docstrings;
        # those are not suppressions
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "analysis")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                if pat.search(line):
                    count += 1
                    context = "\n".join(lines[max(0, i - 3): i + 1])
                    # a justification is a '#' comment beyond the directive
                    stripped = pat.sub("", context)
                    assert "#" in stripped, (
                        f"{path}:{i + 1} suppression lacks a justification "
                        "comment"
                    )
    assert count <= 10, f"{count} suppressions in kserve_tpu/ (budget is 10)"


def test_cli_exits_zero_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "kserve_tpu.analysis", PKG_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_exits_nonzero_on_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(BAD_EXCEPT))
    proc = subprocess.run(
        [sys.executable, "-m", "kserve_tpu.analysis", str(bad)],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 1
    assert "swallowed-exception" in proc.stdout


def test_cli_json_format_round_trips(tmp_path):
    """--format json emits the findings as a machine-parseable list of
    {path,line,col,rule,message} records on stdout, nothing else, and
    the records round-trip to the same content text mode renders."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(BAD_EXCEPT))
    proc = subprocess.run(
        [sys.executable, "-m", "kserve_tpu.analysis", str(bad),
         "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 1
    records = json.loads(proc.stdout)  # stdout must be pure JSON
    assert isinstance(records, list) and records
    for rec in records:
        assert set(rec) == {"path", "line", "col", "rule", "message"}
        assert rec["path"] == str(bad)
        assert isinstance(rec["line"], int) and rec["line"] >= 1
    assert any(r["rule"] == "swallowed-exception" for r in records)

    text_proc = subprocess.run(
        [sys.executable, "-m", "kserve_tpu.analysis", str(bad)],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    rendered = {
        f"{r['path']}:{r['line']}:{r['col']}: [{r['rule']}] {r['message']}"
        for r in records
    }
    assert rendered == set(text_proc.stdout.splitlines())


def test_cli_json_format_clean_is_empty_list():
    proc = subprocess.run(
        [sys.executable, "-m", "kserve_tpu.analysis",
         os.path.join(PKG_DIR, "__init__.py"), "--format", "json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout) == []
