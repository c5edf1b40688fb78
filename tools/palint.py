#!/usr/bin/env python
"""palint — the static program-contract gate.

Checks three things and exits nonzero if any fails:

1. **Program contracts** (`analysis.contracts`): lower the compiled-CG
   lowering matrix (`parallel.tpu.lowering_matrix` — standard / fused /
   block K∈{1,4} × ABFT on-off × strict-bits, plus the f32-staged
   dtype-closure probes) against the fixed (6,6,6)/(2,2,2) probe system
   and check every registered contract: ABFT per-kind collective
   parity, K-independence, block ≤ solo, fused adds no collectives,
   dtype closure, no host transfer inside the loop, the compiled
   copy budget (the PR 2 canary — needs ``--compile``, on by default),
   per-case plan soundness audits, and the static memory budgets
   (`analysis.memory_report`; per-case footprints in ``--report``,
   committed via ``--write-memory`` → MEMORY_FOOTPRINT.json).
2. **Plan soundness** (`analysis.plan_verifier`): statically verify
   every backend's exchange plans on the probe fixtures — the host
   `Exchanger`, the generic index plan (``PA_TPU_BOX=0``) and the box
   slice plan — against the probe operator's sparsity: send/recv
   symmetry, ghost-write race freedom, coverage/dead slots, and
   ppermute-round validity.
3. **Env-key lint** (`analysis.env_lint`): every ``PA_*`` env read in
   the package inventoried; every lowering-affecting one must be
   resolved by a registered cache-key site (`_lowering_env_key` /
   `_gmg_env_key` / `_sdc_config`) and documented in docs/api.md's
   environment table (both directions).

Usage:
    python tools/palint.py --check            # the full gate (CI)
    python tools/palint.py --check --fast     # tier-1 subset
    python tools/palint.py --report           # per-case inventories
    python tools/palint.py --check --no-compile --skip-lint
    python tools/palint.py --check --write-memory  # refresh artifact

Always runs on the CPU host mesh (8 virtual devices), even when real
accelerators are visible — the contracts count STRUCTURE, which is
identical across platforms, and forcing CPU keeps the gate fast and
runnable anywhere.
"""
import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _setup_jax():
    # plain assignment + config.update, NOT setdefault: the contracts
    # count structure, which is identical on the virtual CPU mesh, so
    # the analyzer always runs there — on a machine with a chip too —
    # same pattern as tests/conftest.py.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_ENABLE_X64"] = "true"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    return jax


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="run the full gate (contracts + env lint)")
    ap.add_argument("--report", action="store_true",
                    help="print per-case program inventories")
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 subset of the lowering matrix")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the compiled-HLO copy-budget cases")
    ap.add_argument("--no-runtime", action="store_true",
                    help="skip the probe solves behind the "
                         "static-measured comms reconciliation contract")
    ap.add_argument("--no-memory", action="store_true",
                    help="skip the static memory footprints / budgets")
    ap.add_argument("--skip-matrix", action="store_true",
                    help="skip the contract matrix")
    ap.add_argument("--skip-plans", action="store_true",
                    help="skip the standalone plan-soundness leg")
    ap.add_argument("--skip-lint", action="store_true",
                    help="skip the env-key lint")
    ap.add_argument("--write-memory", metavar="PATH", nargs="?",
                    const=os.path.join(REPO, "MEMORY_FOOTPRINT.json"),
                    default=None,
                    help="write the per-case footprint artifact "
                         "(default: MEMORY_FOOTPRINT.json; implies the "
                         "matrix + memory legs)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if not (args.check or args.report):
        ap.print_help()
        return 2

    failed = False

    from partitionedarrays_jl_tpu.analysis import env_lint

    if not args.skip_lint:
        violations = env_lint.lint_env_keys()
        cls = env_lint.classify()
        n_low = sum(1 for e in cls.values() if e["class"] == "lowering")
        print(
            f"env lint: {len(cls)} PA_* flags inventoried, {n_low} "
            "lowering-affecting, all key-covered"
            if not violations
            else f"env lint: {len(violations)} violation(s)"
        )
        for v in violations:
            print(f"  LINT: {v}")
            failed = True
        if args.verbose and not violations:
            for name, e in sorted(cls.items()):
                keyed = e["keyed_by"] or "-"
                print(f"  {name:32s} {e['class']:9s} keyed_by={keyed}")

    if not args.skip_plans:
        _setup_jax()
        n_plans, defects = _plan_soundness_leg(
            verbose=(lambda m: print(f"  {m}")) if args.verbose else None
        )
        print(
            f"plan soundness: {n_plans} plans verified "
            "(host exchanger, generic index plan, box slice plan)"
            + (
                ", all sound"
                if not defects
                else f", {len(defects)} DEFECT(S)"
            )
        )
        for d in defects:
            print(f"  PLAN: {d}")
            failed = True

    if not args.skip_matrix or args.write_memory:
        _setup_jax()
        from partitionedarrays_jl_tpu.analysis import (
            build_reports,
            check_contracts,
            footprint_table,
        )

        log = (lambda m: print(f"  {m}")) if args.verbose else None
        with_memory = not args.no_memory or bool(args.write_memory)
        cases, reports = build_reports(
            fast=args.fast,
            with_compiled=not args.no_compile,
            with_runtime=not args.no_runtime,
            with_plans=not args.skip_plans,
            with_memory=with_memory,
            verbose=log,
        )
        if args.report or args.verbose:
            for name in sorted(reports):
                print(f"  {name:28s} {reports[name].summary()}")
            if with_memory:
                print("  static memory footprints (B, probe scale):")
                for line in footprint_table(cases).splitlines():
                    print(f"    {line}")
        violations = check_contracts(reports, cases)
        print(
            f"contracts: {len(cases)} cases lowered"
            + ("" if args.no_compile else " (+ compiled copy-budget legs)")
            + ("" if args.no_runtime
               else " (+ runtime comms-reconciliation probes)")
            + ("" if args.skip_plans else " (+ plan audits)")
            + ("" if not with_memory else " (+ memory footprints)")
            + (
                ", all contracts hold"
                if not violations
                else f", {len(violations)} VIOLATION(S)"
            )
        )
        for v in violations:
            print(f"  CONTRACT: {v}")
            failed = True
        if args.write_memory:
            if args.fast:
                print("refusing --write-memory with --fast: the "
                      "committed artifact covers the FULL matrix")
                failed = True
            else:
                from partitionedarrays_jl_tpu.analysis import (
                    memory_report,
                )

                memory_report.write_artifact(
                    args.write_memory, cases, tool="palint"
                )

    if args.check:
        print("palint:", "FAILED" if failed else "OK")
    return 1 if failed else 0


def _plan_soundness_leg(verbose=None):
    """Statically verify every backend's plans over the probe system:
    the host column `Exchanger`, plus the device plan under BOTH env
    flavors (box slice plan under the default env, generic index plan
    under ``PA_TPU_BOX=0``), each against the probe operator's actual
    referenced-ghost sparsity."""
    import jax

    from partitionedarrays_jl_tpu.analysis import plan_verifier as pv
    from partitionedarrays_jl_tpu.parallel.tpu import (
        _MATRIX_BASE_ENV,
        _env_overrides,
        _matrix_probe_system,
        TPUBackend,
        device_matrix,
    )

    backend = TPUBackend(devices=jax.devices()[:8])
    defects, n_plans = [], 0
    for flavor, env in (("box", {}), ("generic", {"PA_TPU_BOX": "0"})):
        e = dict(_MATRIX_BASE_ENV)
        e.update(env)
        with _env_overrides(e):
            A, _b, _x0 = _matrix_probe_system(backend, "f64")
            dA = device_matrix(A, backend)
            ref = pv.referenced_ghosts(A)
            targets = [(f"device-{flavor}", dA.col_plan, None)]
            if flavor == "box":  # host plan is env-independent
                targets.insert(
                    0, ("host-exchanger", A.cols.exchanger,
                        A.cols.partition)
                )
            for nm, plan, parts in targets:
                if verbose:
                    verbose(f"verifying {nm} ...")
                n_plans += 1
                defects.extend(
                    pv.verify_plan(plan, parts=parts, referenced=ref,
                                   name=nm)
                )
    return n_plans, defects


if __name__ == "__main__":
    sys.exit(main())
