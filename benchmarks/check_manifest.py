#!/usr/bin/env python3
"""Offline check of ``BENCHMARK.json`` against every rule of the benchmark's
contract that needs no chip.  ``python3 benchmarks/check_manifest.py`` exits
0 and prints ``manifest ok``, or lists every problem and exits 1.

First of all the rule that refused PR 22: a per-layer metric names ONE
end-to-end metric it moves, and every workload that reports the per-layer
metric must report that end-to-end metric too.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state_size|"
                   r"head_size|head_dim|expansion|experts_per_tok")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
MAX_CELLS, FULL_CHECK_S = 24, 43200


def line(text, what, problems, limit=200):
    if not isinstance(text, str) or not 1 <= len(text) <= limit \
            or "\n" in text or "\t" in text:
        problems.append(f"{what}: needs 1..{limit} characters on one line, "
                        f"no tab")


def check(manifest: dict, root: Path) -> list[str]:
    p: list[str] = []
    if set(manifest) != KEYS["top"]:
        p.append(f"top-level keys {sorted(manifest)} != {sorted(KEYS['top'])}")
        return p
    if len(json.dumps(manifest)) > 64 * 1024:
        p.append("manifest over 64 KiB")

    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        p.append("paths: 1 to 16 directories")
    for d in paths:
        if not PATH.match(d) or d.startswith("/") or ".." in d.split("/"):
            p.append(f"path {d!r}: relative, letters digits _ . - / only")
        elif not (root / d).is_dir():
            p.append(f"path {d!r} is not a directory")
    def under(f):
        return any(f == d or f.startswith(d.rstrip("/") + "/")
                   for d in paths)

    cmd = manifest["command"]
    if not 1 <= len(cmd) <= 32:
        p.append("command: 1 to 32 strings")
    for word in cmd:
        line(word, f"command word {word!r}", p)
        if word.startswith("/") or ".." in word.split("/"):
            p.append(f"command word {word!r} leaves the repo")
        elif (root / word).exists() and "/" in word and not under(word):
            p.append(f"command names {word!r}, a file outside paths")

    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        p.append("run_seconds: a whole number from 1 to 51")
    elif (2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200 \
            > FULL_CHECK_S:
        p.append(f"run_seconds {rs}: a full check of {MAX_CELLS} cells does "
                 f"not fit {FULL_CHECK_S}s")

    def entries(section, kind, lo, hi, extra=()):
        rows = manifest[section]
        if not lo <= len(rows) <= hi:
            p.append(f"{section}: {lo} to {hi} entries")
        names = [r.get("name") for r in rows]
        for n in {n for n in names if names.count(n) > 1}:
            p.append(f"{section}: name {n!r} twice")
        for r in rows:
            allowed = KEYS[kind] | set(extra)
            if not KEYS[kind] <= set(r) or not set(r) <= allowed:
                p.append(f"{section} {r.get('name')!r}: keys {sorted(r)} "
                         f"must be {sorted(KEYS[kind])}"
                         + (f" (+ {sorted(extra)})" if extra else ""))
            if not NAME.match(str(r.get("name", ""))):
                p.append(f"{section}: bad name {r.get('name')!r}")
        return rows

    configs = entries("configs", "config", 1, 24)
    cells = entries("workloads", "workload", 1, 24)
    e2e = entries("end_to_end", "end_to_end", 1, 16, extra=("workloads",))
    layers = entries("per_layer", "per_layer", 1, 128, extra=("workloads",))
    if p:
        return p

    metric_names = [m["name"] for m in e2e + layers]
    for n in {n for n in metric_names if metric_names.count(n) > 1}:
        p.append(f"metric name {n!r} twice")

    files = [c["file"] for c in configs]
    for c in configs:
        line(c["source"], f"config {c['name']} source", p)
        line(c["why"], f"config {c['name']} why", p)
        if files.count(c["file"]) > 1:
            p.append(f"config file {c['file']} used twice")
        if not under(c["file"]) or not (root / c["file"]).is_file():
            p.append(f"config {c['name']}: file {c['file']} missing or "
                     f"outside paths")
        else:
            try:
                body = json.loads((root / c["file"]).read_text())
                for key in ("source", "reduced", "assumed", "guarantees",
                            "sm_config", "ds_config", "dataset", "chips"):
                    if key not in body:
                        p.append(f"{c['file']} lacks {key!r}")
                if body.get("reduced") != c["reduced"]:
                    p.append(f"{c['file']}: reduced differs from the "
                             f"manifest's")
            except ValueError as exc:
                p.append(f"{c['file']}: not JSON ({exc})")
        if len(c["reduced"]) > 16:
            p.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key):
                p.append(f"config {c['name']}: bad reduced key {key!r}")
            if WIDTH.search(key):
                p.append(f"config {c['name']}: reduced names a width "
                         f"{key!r}")
        if not any(w["config"] == c["name"] for w in cells):
            p.append(f"config {c['name']} is used by no cell")

    config_names = {c["name"] for c in configs}
    pairs = [(w["config"], w["traffic"]) for w in cells]
    for w in cells:
        line(w["why"], f"cell {w['name']} why", p)
        if w["config"] not in config_names:
            p.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(str(w["traffic"])):
            p.append(f"cell {w['name']}: bad traffic name")
        if w["chips"] not in (1, 4):
            p.append(f"cell {w['name']}: chips must be 1 or 4")
        if pairs.count((w["config"], w["traffic"])) > 1:
            p.append(f"cell {w['name']}: its (config, traffic) pair twice")
        if not any((root / d / "traffic" / f"{w['traffic']}{ext}").is_file()
                   for d in paths for ext in TRAFFIC_EXT):
            p.append(f"cell {w['name']}: no traffic file for "
                     f"{w['traffic']!r}")
        conf = next((c for c in configs if c["name"] == w["config"]), None)
        if conf and (root / conf["file"]).is_file():
            body = json.loads((root / conf["file"]).read_text())
            if body.get("chips") != w["chips"]:
                p.append(f"cell {w['name']}: {w['chips']} chips, its "
                         f"configuration's file says {body.get('chips')}")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 2):
        p.append(f"{four} of {len(cells)} cells ask for 4 chips: at most "
                 f"50%, rounded down (one always may)")

    cell_names = [w["name"] for w in cells]

    def where(metric):
        ws = metric.get("workloads", cell_names)
        for w in ws:
            if w not in cell_names:
                p.append(f"metric {metric['name']}: unknown workload {w!r}")
        return [w for w in ws if w in cell_names]

    for m in e2e + layers:
        if not UNIT.match(str(m["unit"])):
            p.append(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            p.append(f"metric {m['name']}: better is lower or higher")
        if m["source"] not in SOURCES:
            p.append(f"metric {m['name']}: unknown source {m['source']!r}")
    e2e_where = {m["name"]: where(m) for m in e2e}
    if "setup_s" not in e2e_where:
        p.append("end_to_end lacks setup_s")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            p.append(f"end-to-end {m['name']}: source host_clock or "
                     f"device_trace only")
        b = m["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            p.append(f"end-to-end {m['name']}: bound from 0.01 to 0.25")
    for m in layers:
        line(m["layer"], f"per-layer {m['name']} layer", p)
        if m["moves"] not in e2e_where:
            p.append(f"per-layer {m['name']}: moves {m['moves']!r}, which "
                     f"is no end-to-end metric")
            continue
        for w in where(m):
            if w not in e2e_where[m["moves"]]:
                p.append(
                    f"per_layer metric {m['name']} is reported on workload "
                    f"{w}, where {m['moves']}, which it should move, is not")
        if not any((root / d / "layers" / f"{m['name']}.py").is_file()
                   for d in paths):
            p.append(f"per-layer {m['name']}: no reader "
                     f"layers/{m['name']}.py")
    for w in cell_names:
        if w not in e2e_where.get("setup_s", []):
            p.append(f"cell {w} does not report setup_s")
        if not any(w in ws for n, ws in e2e_where.items() if n != "setup_s"):
            p.append(f"cell {w} reports no end-to-end metric but setup_s")
        if not any(w in where(m) for m in layers):
            p.append(f"cell {w} reports no per-layer metric")
    return p


def main(argv: list[str]) -> int:
    root = Path(argv[0]) if argv else ROOT
    try:
        manifest = json.loads((root / "BENCHMARK.json").read_text())
    except (OSError, ValueError) as exc:
        print(f"BENCHMARK.json: {exc}")
        return 1
    problems = check(manifest, root)
    for line_ in problems:
        print(line_)
    print("manifest ok" if not problems else
          f"{len(problems)} problem(s) in BENCHMARK.json")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
