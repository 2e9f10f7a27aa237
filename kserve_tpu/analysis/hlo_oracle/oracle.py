"""Collection: lower + compile every budgeted program and extract its
metrics entry.

The variant matrix covers the program set `build_compiled` produces in
production shapes that matter structurally: the tp=1 full set (both
prefill buckets), the speculative mixed_decode at K=2 and the K=0
dense-packing degenerate, the quantized-cache inject, and a tp=2 mesh
slice whose collective inventory pins the model-axis communication
pattern.  Compiles run on CPU with jax's persistent compilation cache
(the CLI and conftest share /tmp/kserve-tpu-compile-cache), so warm
re-runs cost milliseconds per program.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import jax

from . import extract, signatures

logger = logging.getLogger(__name__)

#: current baseline schema; bump on layout changes so a stale committed
#: perf_budgets.json asks for `update` instead of mis-diffing
SCHEMA_VERSION = 1

#: programs whose costs scale with the prefill length bucket: one entry
#: per configured bucket
_BUCKETED = ("prefill", "prefill_chunk")

#: (variant name, ProgramSet kwargs, program names) — None = every
#: program the variant's defs table builds
VARIANTS: List[Tuple[str, dict, Optional[Tuple[str, ...]]]] = [
    ("tp1", dict(tp=1), None),
    ("tp1_spec", dict(tp=1, spec_k=2), ("mixed_decode",)),
    ("tp1_spec0", dict(tp=1, spec_k=0), ("mixed_decode",)),
    ("tp1_q", dict(tp=1, kv_quant="int8"), ("inject_q",)),
    ("tp2", dict(tp=2),
     ("prefill", "prefill_chunk", "decode", "inject", "mixed")),
    ("tp2_spec", dict(tp=2, spec_k=2), ("mixed_decode",)),
]


def program_keys(variant: str, name: str, ps) -> List[Tuple[str, Optional[int]]]:
    """Budget keys (and their bucket arg) for one program under one
    variant: bucketed programs fan out per prefill bucket, mixed_decode
    is keyed by its K."""
    if name in _BUCKETED:
        return [(f"{variant}/{name}/b{b}", b)
                for b in ps.shapes.token_buckets]
    if name == "mixed_decode":
        return [(f"{variant}/{name}/k{ps.spec_k or 0}", None)]
    return [(f"{variant}/{name}", None)]


def extract_program(fn, args, donate_argnums, norm=None) -> dict:
    """Lower + compile one program and extract its entry.

    keep_unused=True is load-bearing: jit's default prunes unused args
    and renumbers HLO parameters, which would break the donated-arg ->
    parameter-index mapping the alias check depends on.  Cost metrics
    are unaffected (the kept params are inputs, not compute)."""
    jitted = jax.jit(fn, donate_argnums=donate_argnums, keep_unused=True)
    compiled = jitted.lower(*args).compile()
    return extract.compiled_report(
        compiled, args=args, donate_argnums=donate_argnums, norm=norm)


def collect(only: Optional[str] = None,
            defs_override=None) -> Dict[str, dict]:
    """The full {program key: metrics entry} map.  `only` substring-
    filters program keys (fast dev/test iteration); `defs_override`
    swaps the program_defs table builder (the seeded-mutation test's
    hook)."""
    out: Dict[str, dict] = {}
    for variant, ps_kwargs, names in VARIANTS:
        ps = None  # built lazily: an `only` filter skips whole variants
        for name, key, bucket in _variant_programs(
                variant, ps_kwargs, names, only):
            if ps is None:
                ps = signatures.build_program_set(**ps_kwargs)
                if defs_override is not None:
                    ps.defs = defs_override(
                        ps.mc, ps.cfg, ps.mesh, spec_k=ps.spec_k)
            if name not in ps.defs:
                logger.warning("oracle: %s has no %s program; skipped",
                               variant, name)
                continue
            fn, donate = ps.defs[name]
            args, norm = signatures.args_for(ps, name, bucket=bucket)
            logger.info("oracle: compiling %s", key)
            out[key] = extract_program(fn, args, donate, norm=norm)
    return out


def _variant_programs(variant: str, ps_kwargs: dict, names, only):
    """(name, key, bucket) triples for one variant, pre-filtered by
    `only` WITHOUT building the program set (key shapes depend only on
    the config, so a filtered run skips whole variants for free)."""
    cfg = signatures.tiny_engine_config(
        **{k: v for k, v in ps_kwargs.items() if k != "spec_k"})
    spec_k = ps_kwargs.get("spec_k")

    class _KeyShim:
        pass

    shim = _KeyShim()
    shim.shapes = signatures.DispatchShapes.of(
        signatures.tiny_model_config(), cfg, jax.default_backend())
    shim.spec_k = spec_k
    if names is None:
        names = _default_program_names(cfg, spec_k)
    for name in names:
        for key, bucket in program_keys(variant, name, shim):
            if only and only not in key:
                continue
            yield name, key, bucket


def _default_program_names(cfg, spec_k) -> Tuple[str, ...]:
    """The program names program_defs builds for this config, WITHOUT
    tracing anything: mirrors the defs-table gating in compiled.py
    (kept trivially in sync by test_hlo_oracle's key-coverage test)."""
    names = [
        "prefill", "prefill_lp", "prefill_chunk",
        "sample_first", "sample_first_lp",
        "decode", "decode_lp", "decode_penalized", "decode_penalized_lp",
        "inject", "inject_q",
    ]
    if cfg.pp == 1:
        names.append("mixed")
        if spec_k is not None:
            names.append("mixed_decode")
    if cfg.kv_quant != "int8":
        # inject_q's signature needs the quantized cache; the tp1_q
        # variant budgets it, every other variant skips it
        names.remove("inject_q")
    return tuple(names)


def environment_stamp() -> dict:
    import jaxlib

    return {
        "schema_version": SCHEMA_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
    }
