"""CLI: ``python -m kserve_tpu.analysis.hlo_oracle check|update|diff``.

check   compile the canonical program set, compare against the committed
        perf_budgets.json; exit 1 with a per-program delta report on any
        budget violation.  Degrades to a SKIP (exit 0, warning printed)
        when jax is unavailable, the backend differs from the baseline's,
        or this jax reports no cost_analysis fields — the gate must
        never block on backend drift.
update  re-collect and overwrite perf_budgets.json (commit the result).
diff    print the full delta table without gating.

The jax environment is pinned BEFORE jax imports — CPU backend, 8
virtual devices, the shared persistent compilation cache — so the CLI,
the test suite, and the AOT seam all hit the same compile cache and the
oracle re-run cost is milliseconds per warm program.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

_log = logging.getLogger(__name__)


def _pin_jax_env() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")


def _init_jax() -> bool:
    try:
        import jax
    except Exception as exc:  # jax-less envs skip, not fail
        _log.debug("jax import failed", exc_info=True)
        print(f"hlo_oracle: SKIP — jax unavailable ({exc})")
        return False
    # the budgets are CPU-lowered by definition, whatever the host offers
    jax.config.update("jax_platforms", "cpu")
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("KSERVE_TPU_COMPILE_CACHE",
                       "/tmp/kserve-tpu-compile-cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return True


def _print_report(cmp, verbose: bool) -> None:
    if verbose or not cmp.ok:
        for line in cmp.deltas:
            print(f"  {line}")
    for w in cmp.warnings:
        print(f"hlo_oracle: WARNING {w}")
    for v in cmp.violations:
        print(f"hlo_oracle: VIOLATION {v}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m kserve_tpu.analysis.hlo_oracle",
        description="HLO perf oracle: per-program FLOP/byte, "
        "donation-alias, and collective budgets",
    )
    parser.add_argument("command", choices=("check", "update", "diff"))
    parser.add_argument(
        "--budgets", default=None,
        help="baseline path (default: repo-root perf_budgets.json)")
    parser.add_argument(
        "--only", default=None,
        help="substring filter on program keys (fast partial runs; "
        "check compares only the matching baseline entries)")
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="print the full delta table even when clean")
    args = parser.parse_args(argv)

    _pin_jax_env()
    if not _init_jax():
        return 0

    from . import budgets, oracle

    path = args.budgets or budgets.DEFAULT_BUDGETS_PATH
    stamp = oracle.environment_stamp()

    if args.command == "update":
        programs = oracle.collect(only=args.only)
        if args.only:
            # partial update: merge into the existing baseline so an
            # `--only` iteration never drops the other budgets
            doc = budgets.load_budgets(path)
            merged = dict(doc.get("programs", {})) if doc else {}
            merged.update(programs)
            programs = merged
        budgets.write_budgets(programs, stamp, path=path)
        print(f"hlo_oracle: wrote {len(programs)} program budgets to "
              f"{path} (jax {stamp['jax']}, backend {stamp['backend']})")
        return 0

    baseline = budgets.load_budgets(path)
    if baseline is None:
        print(f"hlo_oracle: no baseline at {path} — run "
              "`python -m kserve_tpu.analysis.hlo_oracle update` and "
              "commit it")
        return 1
    if baseline.get("schema_version") != oracle.SCHEMA_VERSION:
        print(
            f"hlo_oracle: baseline schema_version="
            f"{baseline.get('schema_version')} != {oracle.SCHEMA_VERSION} "
            "— run update and commit the regenerated perf_budgets.json")
        return 1
    if baseline.get("backend") != stamp["backend"]:
        print(
            f"hlo_oracle: SKIP — baseline was built on backend="
            f"{baseline.get('backend')!r}, this env is "
            f"{stamp['backend']!r}; budgets only compare like-for-like")
        return 0
    if baseline.get("jax") != stamp["jax"]:
        print(
            f"hlo_oracle: note — baseline jax {baseline.get('jax')} vs "
            f"installed {stamp['jax']}; version-drift deltas within "
            "tolerance are absorbed, run update to re-stamp")

    programs = oracle.collect(only=args.only)
    if not any("flops" in entry for entry in programs.values()):
        print(
            "hlo_oracle: SKIP — this jax reports no cost_analysis "
            "fields; FLOP/byte budgets cannot be checked here "
            f"(jax {stamp['jax']}, backend {stamp['backend']})")
        return 0
    cmp = budgets.compare(baseline, programs, only=args.only)

    if args.command == "diff":
        _print_report(cmp, verbose=True)
        print(f"hlo_oracle: {len(cmp.violations)} violation(s), "
              f"{len(cmp.warnings)} warning(s) across "
              f"{len(programs)} program(s)")
        return 0

    _print_report(cmp, verbose=args.verbose)
    if cmp.ok:
        print(f"hlo_oracle: clean — {len(programs)} program(s) within "
              "budget")
        return 0
    print(f"hlo_oracle: {len(cmp.violations)} budget violation(s)")
    return 1


if __name__ == "__main__":
    sys.exit(main())
