#!/usr/bin/env python
"""Docs-consistency gate: CLI flags and artifacts mentioned must exist.

Three checks:

- every ``--flag`` token in README.md and docs/*.md appears in the
  ``--help`` output of the CLIs the docs describe (``repro.launch.fleet``
  plus the ``benchmarks.fleet_*`` suites and ``chip_smoke`` — see
  ``CLIS``) — catches the
  classic drift where a flag is renamed or removed but the prose keeps
  recommending it;
- every committed ``experiments/*.json`` artifact has a schema entry in
  ``docs/experiments.md`` (its filename is mentioned there) — catches
  benchmarks that grow a new artifact without documenting its fields;
- every committed ``experiments/*.json`` artifact carries the ``host``
  provenance block (``benchmarks.common.host_metadata()`` — platform,
  CPU, JAX version/backend) so recorded numbers are attributable to a
  machine; Chrome-trace exports (files with a ``traceEvents`` key) are
  structurally exempt — their schema is fixed by the trace viewer;
- every telemetry channel named in docs/observability.md's catalog
  exists in ``repro.obs.state.TELE_FIELDS``, and every field is
  cataloged — the channel table and the code cannot drift apart;
- every kernel in the ``repro.kernels.KERNELS`` registry has a row in
  docs/kernels.md's kernel table, and every row names a registered
  kernel — adding a kernel module without documenting it (or
  documenting a removed one) fails here.

Run from the repo root:

    PYTHONPATH=src python tools/check_docs.py

(CI runs it after the fleet smoke; an editable install makes PYTHONPATH
unnecessary.)
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CLIS = ("repro.launch.fleet", "benchmarks.fleet_throughput",
        "benchmarks.fleet_quality", "benchmarks.fleet_observability",
        "benchmarks.fleet_megakernel", "benchmarks.fleet_sharded_scaling",
        "benchmarks.fleet_streaming", "benchmarks.fleet_exactness",
        "chip_smoke")
DOCS = ("README.md", "docs")

# `--flag` with a word boundary before it (skips ---- rules and
# mid-word dashes); flags are lowercase kebab-case in this repo
FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")


def help_text(module: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run([sys.executable, "-m", module, "--help"],
                        capture_output=True, text=True, env=env,
                        cwd=ROOT)
    if res.returncode != 0:
        sys.stderr.write(res.stderr)
        raise SystemExit(f"--help failed for {module}")
    return res.stdout


def doc_flags() -> dict[str, list[str]]:
    found: dict[str, list[str]] = {}
    files: list[Path] = []
    for entry in DOCS:
        p = ROOT / entry
        files.extend(sorted(p.glob("*.md")) if p.is_dir() else [p])
    for f in files:
        for flag in FLAG_RE.findall(f.read_text()):
            found.setdefault(flag, []).append(str(f.relative_to(ROOT)))
    return found


def undocumented_artifacts() -> list[str]:
    """Committed experiments/*.json files whose filenames never appear
    in docs/experiments.md (no schema entry)."""
    schema_doc = ROOT / "docs" / "experiments.md"
    text = schema_doc.read_text() if schema_doc.exists() else ""
    return sorted(p.name for p in (ROOT / "experiments").glob("*.json")
                  if p.name not in text)


def unattributed_artifacts() -> list[str]:
    """Committed experiments/*.json files missing the ``host``
    provenance block. Chrome-trace exports (top-level ``traceEvents``)
    have a viewer-fixed schema and are exempt."""
    import json
    bad = []
    for p in sorted((ROOT / "experiments").glob("*.json")):
        doc = json.loads(p.read_text())
        if "traceEvents" in doc:
            continue
        if "host" not in doc:
            bad.append(p.name)
    return bad


def channel_catalog_drift() -> tuple[list[str], list[str]]:
    """(unknown, uncataloged): channel names docs/observability.md's
    catalog table lists that TeleState lacks, and TeleState fields the
    catalog never mentions. repro.obs.state imports nothing beyond
    numpy, so this stays cheap."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.obs.state import TELE_FIELDS
    doc = (ROOT / "docs" / "observability.md").read_text()
    # catalog rows: "| `name` | accumulated/sampled | ..."
    cataloged = set(re.findall(
        r"^\|\s*`(\w+)`\s*\|\s*(?:accumulated|sampled)\s*\|", doc,
        re.MULTILINE))
    fields = set(TELE_FIELDS)
    return sorted(cataloged - fields), sorted(fields - cataloged)


def kernel_registry_drift() -> tuple[list[str], list[str]]:
    """(unknown, undocumented): kernels docs/kernels.md's table lists
    that the registry lacks, and registered kernels the table never
    mentions. repro.kernels imports nothing heavy at module level."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import KERNELS
    doc = (ROOT / "docs" / "kernels.md").read_text()
    # table rows: "| `name` | purpose | ..."
    documented = set(re.findall(r"^\|\s*`(\w+)`\s*\|", doc, re.MULTILINE))
    registry = set(KERNELS)
    return sorted(documented - registry), sorted(registry - documented)


def main() -> int:
    known = set()
    for module in CLIS:
        known |= set(FLAG_RE.findall(help_text(module)))
    found = doc_flags()
    missing = {flag: sorted(set(where))
               for flag, where in sorted(found.items())
               if flag not in known}
    if missing:
        print("docs mention CLI flags that no CLI --help declares:",
              file=sys.stderr)
        for flag, where in missing.items():
            print(f"  {flag}  (in {', '.join(where)})", file=sys.stderr)
        return 1
    undoc = undocumented_artifacts()
    if undoc:
        print("experiments/*.json artifacts with no schema entry in "
              "docs/experiments.md:", file=sys.stderr)
        for name in undoc:
            print(f"  {name}", file=sys.stderr)
        return 1
    unattributed = unattributed_artifacts()
    if unattributed:
        print("experiments/*.json artifacts missing the host_metadata() "
              "provenance block (a top-level \"host\" key):",
              file=sys.stderr)
        for name in unattributed:
            print(f"  {name}", file=sys.stderr)
        return 1
    unknown, uncataloged = channel_catalog_drift()
    if unknown or uncataloged:
        if unknown:
            print("docs/observability.md catalogs channels TeleState "
                  f"does not have: {', '.join(unknown)}", file=sys.stderr)
        if uncataloged:
            print("TeleState channels missing from the "
                  "docs/observability.md catalog: "
                  f"{', '.join(uncataloged)}", file=sys.stderr)
        return 1
    k_unknown, k_undoc = kernel_registry_drift()
    if k_unknown or k_undoc:
        if k_unknown:
            print("docs/kernels.md documents kernels the "
                  "repro.kernels.KERNELS registry does not have: "
                  f"{', '.join(k_unknown)}", file=sys.stderr)
        if k_undoc:
            print("registered kernels missing from the docs/kernels.md "
                  f"table: {', '.join(k_undoc)}", file=sys.stderr)
        return 1
    print(f"docs-consistency OK: {len(found)} doc flags all exist "
          f"in {' + '.join(CLIS)} --help; all experiments/*.json "
          "artifacts documented and host-attributed; telemetry channel "
          "catalog matches TeleState; kernel registry matches "
          "docs/kernels.md")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
