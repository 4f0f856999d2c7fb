#!/usr/bin/env python3
"""On-chip acceptance of a committed tree: cache placement and the refusals.

Run it through the chip tool on an unpacked ``git archive`` (the driver's
checkout holds only what git commits)::

    mkdir -p _checkout/pr
    git archive $(git write-tree) | tar -x -C _checkout/pr
    chiprun --timeout 2400 -- python _checkout/pr/scripts/chip_accept.py

It runs ``chip_smoke.py`` four times, one after another — twice with
``JAX_COMPILATION_CACHE_DIR`` unset (the cache must resolve to
``<checkout>/.jax_cache``), twice with it set to a directory of its own — and
expects the second run of each pair to find a non-empty cache and to warm up
faster than the first.  Then the smoke alone in an empty directory and the
smoke pinned to the CPU, which must both fail and print nothing.  Everything
each run printed lands under ``chiprun_out/``.  Stdlib only: this process
never imports jax, so it never holds the chip its children need.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the chip tool runs the command from the root of its copy and brings
#: back what lands in ``chiprun_out/`` there
OUT = os.path.abspath("chiprun_out")


def run_smoke(name: str, env: dict, cwd: str = CHECKOUT,
              script: str = "chip_smoke.py") -> tuple:
    p = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=1300)
    os.makedirs(OUT, exist_ok=True)
    for ext, text in (("out", p.stdout), ("err", p.stderr)):
        with open(os.path.join(OUT, f"accept_{name}.{ext}"), "w") as f:
            f.write(text)
    return p.returncode, p.stdout, p.stderr


def main() -> int:
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    print("JAX_COMPILATION_CACHE_DIR on this machine:",
          repr(os.environ.get("JAX_COMPILATION_CACHE_DIR", "<unset>")))
    given = os.path.join(os.path.dirname(CHECKOUT), "given_cache")
    ok = True
    for pair, env, want_dir in (
            ("unset", base, os.path.join(CHECKOUT, ".jax_cache")),
            ("set", dict(base, JAX_COMPILATION_CACHE_DIR=given), given)):
        walls = []
        for k in (1, 2):
            rc, out, _err = run_smoke(f"{pair}{k}", env)
            lines = out.strip().splitlines()
            print(f"{pair}{k} rc {rc} last_line {lines[-1] if lines else ''}")
            if rc != 0 or len(lines) < 2:
                ok = False
                continue
            s = json.loads(lines[-2])
            cache, side = s["compile_cache"], s["sidecar"]
            walls.append(side["warmup_wall_s"])
            print("   cache", cache, "warmup_wall_s", side["warmup_wall_s"],
                  "ready_after_s", side["ready_after_s"], "programs",
                  side["programs_compiled"], "wall_s", s["wall_s"])
            print("   requests", [(r["request"], r["wall_ms"], r["tiers"],
                                   r["relax"]) for r in
                                  s["client"]["requests"]])
            print("   delta", s["client"]["delta"]["establish_ms"],
                  s["client"]["delta"]["step_ms"])
            print("   device", s["device_direct"])
            print("   processes", s["processes"])
            ok &= cache["dir"] == want_dir
            ok &= (cache["entries_before"] > 0) == (k == 2)
        if len(walls) == 2:
            print(f"   {pair}: warm-up {walls[0]} s -> {walls[1]} s")
            ok &= walls[1] < walls[0]

    print("--- alone in a directory")
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(os.path.join(CHECKOUT, "chip_smoke.py"), d)
        rc, out, err = run_smoke("alone", base, cwd=d)
    print(f"alone rc={rc} stdout_bytes={len(out)}", err.strip()[-300:])
    ok &= rc != 0 and not out.strip()
    print("--- pinned to the CPU")
    rc, out, err = run_smoke("cpu", dict(base, JAX_PLATFORMS="cpu"))
    print(f"cpu rc={rc} stdout_bytes={len(out)}", err.strip()[-300:])
    ok &= rc != 0 and not out.strip()
    print("ACCEPT", "ok" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
