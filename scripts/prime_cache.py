#!/usr/bin/env python
"""Offline AOT cache priming (ISSUE 13).

Reads the shape-bucket lattice manifest (``bucket_manifest.json`` next to
the persistent XLA cache — written by the jax backends as traffic records
``BucketSpec``s, see ``ops/buckets.py``) and AOT-compiles every flat-path
spec into the persistent compilation cache, so a freshly deployed replica
serves its first submit from primed executables instead of paying the
cold XLA compile.  The in-service equivalent is the scheduler-idle
``CachePrimer`` thread (``service.prime`` config); this CLI exists for
deploy pipelines and for re-priming after a jax/backend upgrade (primed
entries are environment-keyed).

Usage::

    python scripts/prime_cache.py --sm-config conf/config.json
    JAX_COMPILATION_CACHE_DIR=/srv/sm/xla python scripts/prime_cache.py --force
    python scripts/prime_cache.py --spec '{"kind":"flat", ...}'  # ad hoc

Prints ONE JSON summary line on stdout ({known, compiled, skipped,
errors, cache_dir}); logging goes to stderr.  Exit 0 unless a compile
errored (exit 1) or nothing was known to prime (exit 2 — run traffic or
pass --spec first).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="prime_cache")
    ap.add_argument("--sm-config", default=None,
                    help="SMConfig json (default: env/default resolution)")
    ap.add_argument("--force", action="store_true",
                    help="re-prime specs the prime manifest already marks "
                         "primed for this environment")
    ap.add_argument("--spec", action="append", default=[],
                    help="additional BucketSpec JSON object(s) to prime "
                         "(besides the recorded manifest)")
    args = ap.parse_args(argv)

    from sm_distributed_tpu.utils.config import SMConfig
    from sm_distributed_tpu.utils.logger import init_logger

    init_logger()
    sm = (SMConfig.set_path(args.sm_config) if args.sm_config
          else SMConfig.get_conf())

    from sm_distributed_tpu.ops import buckets
    from sm_distributed_tpu.parallel.distributed import compile_cache_path
    from sm_distributed_tpu.service.primer import (
        CachePrimer,
        _env_key,
        prime_spec,
    )

    cache_dir = compile_cache_path(sm)
    if cache_dir is None:
        print(json.dumps({"error": "compile cache disabled "
                                   "(parallel.compile_cache_dir=off)"}))
        return 2
    primer = CachePrimer(sm, busy=lambda: False)
    extra = [json.loads(s) for s in args.spec]
    for spec in extra:
        buckets.record_spec(spec)
    known = primer.known_specs()
    if not known:
        print(json.dumps({"known": 0, "compiled": 0, "skipped": 0,
                          "errors": 0, "cache_dir": str(cache_dir),
                          "note": "no recorded bucket specs — run traffic "
                                  "once or pass --spec"}))
        return 2
    if args.force:
        # bypass the prime manifest: compile everything flat directly
        out = {"compiled": 0, "skipped": 0, "errors": 0}
        env = _env_key()
        for spec in known:
            try:
                status = prime_spec(spec, sm_config=sm)
            except Exception:
                from sm_distributed_tpu.utils.logger import logger

                logger.warning("prime_cache: compile failed for %s",
                               buckets.spec_key(spec), exc_info=True)
                out["errors"] += 1
                continue
            if status == "compiled":
                out["compiled"] += 1
                primer._manifest.mark(buckets.spec_key(spec), env)
            else:
                out["skipped"] += 1
    else:
        out = primer.prime_once(abort_when_busy=False)
    summary = {"known": len(known), **{k: out.get(k, 0) for k in
                                       ("compiled", "skipped", "errors")},
               "cache_dir": str(cache_dir)}
    print(json.dumps(summary))
    return 1 if out.get("errors") else 0


if __name__ == "__main__":
    sys.exit(main())
