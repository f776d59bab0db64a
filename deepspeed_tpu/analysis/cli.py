"""Command-line front end for graftlint (see ``bin/graftlint``).

Exit codes: 0 = gate passes, 1 =
unsuppressed errors above ``--max-errors``, 2 = unusable invocation
(bad path, bad baseline file) — a typo can never pass silently.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from .baseline import write_baseline
from .concurrency_rules import SYNC_RULES
from .ownership_rules import OWN_RULES
from .rules import ALL_RULES, META_RULES
from .runner import analyze_paths, check_paths, effect_inventory, \
    jit_inventory, thread_inventory
from .sharding_rules import SHARDING_RULES

#: the CI gate: these trees hold at zero unsuppressed errors
DEFAULT_GATE_PATHS = ("deepspeed_tpu/serving", "deepspeed_tpu/telemetry",
                      "deepspeed_tpu/parallel",
                      "deepspeed_tpu/runtime/engine.py")

#: interpreter finding ids (not Rule objects — emitted by enumeration)
INTERP_RULE_IDS = ("signature-escape", "unbounded-signature")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _default_paths() -> List[str]:
    # resolve the gate paths relative to the repo root (parent of the
    # package) so `bin/graftlint` works from any cwd
    cands = [os.path.join(_repo_root(), p) for p in DEFAULT_GATE_PATHS]
    return [c for c in cands if os.path.exists(c)]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="Static trace-safety analyzer for the serving stack "
                    "(stdlib ast only — no jax import, runs in "
                    "milliseconds).")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to analyze (default: the CI "
                         "gate — deepspeed_tpu/serving + telemetry)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout "
                         "(schema: {version, summary, findings})")
    ap.add_argument("--baseline", metavar="FILE",
                    help="fingerprint file of grandfathered findings")
    ap.add_argument("--write-baseline", metavar="FILE",
                    help="write current unsuppressed findings as the new "
                         "baseline and exit 0")
    ap.add_argument("--select", action="append", default=[],
                    metavar="RULE", help="run only these rule ids "
                    "(repeatable)")
    ap.add_argument("--ignore", action="append", default=[],
                    metavar="RULE", help="skip these rule ids (repeatable)")
    ap.add_argument("--max-errors", type=int, default=0, metavar="N",
                    help="tolerated unsuppressed+unbaselined errors "
                         "(default 0)")
    ap.add_argument("--tier", choices=("all", "lint", "sync", "own"),
                    default="all",
                    help="rule tier: 'lint' = trace-safety rules only, "
                         "'sync' = graftsync thread-context/async-safety "
                         "rules only, 'own' = graftown ownership/"
                         "exception-path rules only, 'all' (default) = "
                         "every tier")
    ap.add_argument("--threads", action="store_true",
                    help="print the inferred thread-context map "
                         "(qualname -> LOOP|ENGINE|BOTH|EXECUTOR) as "
                         "JSON and exit (graftsync drift check)")
    ap.add_argument("--effects", action="store_true",
                    help="print the graftown effect table plus every "
                         "inferred per-function resource-effect summary "
                         "as JSON and exit (ownership drift check)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--inventory", action="store_true",
                    help="print the static jit-wrapper inventory as JSON "
                         "and exit (watchdog coverage drift check)")
    ap.add_argument("--check", action="store_true",
                    help="the graftcheck tier: lint + sharding rules plus "
                         "the abstract interpreter's signature "
                         "enumeration (finiteness proof); with "
                         "--manifest, also diff static vs runtime "
                         "warmup signatures")
    ap.add_argument("--manifest", metavar="FILE",
                    help="signatures.json warmup manifest exported by "
                         "`ServingEngine.export_signatures` — re-enumerates under "
                         "the manifest's recorded configs and fails on "
                         "any static/runtime divergence (implies "
                         "--check)")
    ap.add_argument("--signatures", nargs="?", const="-", metavar="FILE",
                    help="with --inventory: also emit the statically "
                         "enumerated program -> sorted abstract "
                         "signature list as JSON (to FILE, or stdout "
                         "when bare) — a manifest reproducible without "
                         "jax")
    ap.add_argument("--verbose", action="store_true",
                    help="also print suppressed/baselined findings")
    args = ap.parse_args(argv)

    check_tier = args.check or args.manifest is not None

    if args.list_rules:
        for r in ALL_RULES:
            print(f"{r.id:22s} [{r.severity}] {r.short}")
        for r in SYNC_RULES:
            print(f"{r.id:26s} [{r.severity}] {r.short}  (sync tier)")
        for r in OWN_RULES:
            print(f"{r.id:26s} [{r.severity}] {r.short}  (own tier)")
        for r in SHARDING_RULES:
            print(f"{r.id:22s} [{r.severity}] {r.short}  (--check)")
        for rid in INTERP_RULE_IDS:
            print(f"{rid:22s} [error] abstract signature enumeration  "
                  f"(--check)")
        for rid, desc in META_RULES.items():
            print(f"{rid:22s} [meta]  {desc}")
        return 0

    known = {r.id for r in ALL_RULES} | {r.id for r in SYNC_RULES} \
        | {r.id for r in OWN_RULES}
    if check_tier:
        known |= {r.id for r in SHARDING_RULES} | set(INTERP_RULE_IDS)
        if args.tier != "all":
            print("graftlint: --tier cannot narrow --check (use --select)",
                  file=sys.stderr)
            return 2
    for rid in list(args.select) + list(args.ignore):
        if rid not in known:
            print(f"graftlint: unknown rule id '{rid}' "
                  f"(see --list-rules)", file=sys.stderr)
            return 2

    paths = args.paths or _default_paths()
    if not paths:
        print("graftlint: no paths given and default gate dirs not found",
              file=sys.stderr)
        return 2

    if args.threads:
        try:
            tmap = thread_inventory(paths)
        except FileNotFoundError as e:
            print(f"graftlint: no such path: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"version": 1, "files": tmap},
                         indent=2, sort_keys=True))
        return 0

    if args.effects:
        try:
            emap = effect_inventory(paths)
        except FileNotFoundError as e:
            print(f"graftlint: no such path: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"version": 1, **emap},
                         indent=2, sort_keys=True))
        return 0

    if args.inventory:
        try:
            inv = jit_inventory(paths)
        except FileNotFoundError as e:
            print(f"graftlint: no such path: {e}", file=sys.stderr)
            return 2
        if args.signatures:
            from .interp import default_check_envs, enumerate_union
            envs = default_check_envs()
            res = enumerate_union(envs, _repo_root())
            doc = {"version": 1, "configs": envs,
                   "programs": {k: sorted(v)
                                for k, v in sorted(res.programs.items())}}
            if args.signatures == "-":
                print(json.dumps(doc, indent=2, sort_keys=True))
            else:
                with open(args.signatures, "w", encoding="utf-8") as fh:
                    json.dump(doc, fh, indent=2, sort_keys=True)
                    fh.write("\n")
                print(f"graftlint: wrote {sum(map(len, res.programs.values()))}"
                      f" signature(s) across {len(res.programs)} program(s)"
                      f" to {args.signatures}")
        else:
            print(json.dumps(inv, indent=2))
        return 0

    manifest = None
    if args.manifest is not None:
        try:
            with open(args.manifest, encoding="utf-8") as fh:
                manifest = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"graftlint: cannot read manifest: {e}", file=sys.stderr)
            return 2
        if not isinstance(manifest.get("programs"), dict):
            print(f"graftlint: {args.manifest} is not a signatures.json "
                  "manifest (missing 'programs')", file=sys.stderr)
            return 2

    try:
        if check_tier:
            envs = manifest.get("configs") if manifest else None
            report = check_paths(paths, root=_repo_root(),
                                 envs=envs or None,
                                 select=args.select or None,
                                 ignore=args.ignore or None,
                                 baseline=args.baseline)
        else:
            tier_rules = None            # "all": lint + sync + own
            if args.tier == "lint":
                tier_rules = ALL_RULES
            elif args.tier == "sync":
                tier_rules = SYNC_RULES
            elif args.tier == "own":
                tier_rules = OWN_RULES
            report = analyze_paths(paths, select=args.select or None,
                                   ignore=args.ignore or None,
                                   baseline=args.baseline,
                                   rules=tier_rules)
    except FileNotFoundError as e:
        print(f"graftlint: no such path: {e}", file=sys.stderr)
        return 2
    except (OSError, ValueError) as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2

    manifest_diffs: List[str] = []
    if manifest is not None:
        from .interp import default_check_envs, diff_manifest, \
            enumerate_union
        envs = manifest.get("configs") or default_check_envs()
        res = enumerate_union(envs, _repo_root())
        static = {k: sorted(v) for k, v in res.programs.items()}
        manifest_diffs = diff_manifest(static, manifest["programs"])

    if args.write_baseline:
        n = write_baseline(args.write_baseline, report.findings)
        print(f"graftlint: wrote {n} finding(s) to {args.write_baseline}")
        return 0

    if args.json:
        doc = json.loads(report.to_json())
        if manifest is not None:
            doc["manifest"] = {"path": args.manifest,
                               "diffs": manifest_diffs}
        print(json.dumps(doc, indent=2))
    else:
        print(report.format_human(verbose=args.verbose))
        if manifest is not None:
            if manifest_diffs:
                print(f"manifest divergence vs {args.manifest}:")
                for d in manifest_diffs:
                    print(f"  {d}")
            else:
                print(f"manifest: static signature set matches "
                      f"{args.manifest} exactly")

    if manifest_diffs:
        return 1
    return 1 if report.errors > args.max_errors else 0
