#!/usr/bin/env python
"""Generate API_REFERENCE.md: every public entry point with its one-line
summary, grouped by surface (core drivers, distributed layer, compat skins,
C/Fortran ABIs).  Run after adding routines; CI-adjacent sync is pinned by
tests/test_api_reference.py (the file must be regenerable byte-identical)."""

import inspect
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def first_line(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    # Enum subclasses on some interpreters report the stock "An enumeration."
    # instead of inheriting the base's docstring — walk the MRO for the first
    # informative one so regeneration doesn't churn those rows with filler.
    if doc.startswith("An enumeration") and isinstance(obj, type):
        for base in obj.__mro__[1:]:
            bd = base.__doc__ or ""
            if bd and not bd.startswith("An enumeration") \
                    and base.__module__.startswith("slate_tpu"):
                doc = bd
                break
    line = doc.strip().split("\n")[0].strip()
    return re.sub(r"\s+", " ", line)


def rows_for(mod, names):
    out = []
    for name in sorted(names):
        obj = getattr(mod, name, None)
        if obj is None or not callable(obj):
            continue
        out.append(f"| `{name}` | {first_line(obj)} |")
    return out


def section(title, lines):
    return [f"## {title}", "", "| entry point | summary |", "|---|---|",
            *lines, ""]


def main():
    import slate_tpu
    import slate_tpu.parallel as par
    from slate_tpu import lapack_api, scalapack_api

    md = ["# slate_tpu API reference",
          "",
          "Generated by `tools/gen_api_reference.py` — regenerate after adding",
          "routines (`python tools/gen_api_reference.py`).  One row per public",
          "entry point; the summary is the first docstring line.",
          ""]

    top = [n for n in dir(slate_tpu)
           if not n.startswith("_") and callable(getattr(slate_tpu, n))
           and getattr(getattr(slate_tpu, n), "__module__",
                       "").startswith("slate_tpu")]
    md += section("Python drivers (`slate_tpu`)", rows_for(slate_tpu, top))

    pnames = [n for n in dir(par)
              if not n.startswith("_") and callable(getattr(par, n))
              and getattr(getattr(par, n), "__module__",
                          "").startswith("slate_tpu")]
    md += section("Distributed layer (`slate_tpu.parallel`)",
                  rows_for(par, pnames))

    import slate_tpu.robust as rob
    rnames = [n for n in getattr(rob, "__all__", dir(rob))
              if not n.startswith("_") and callable(getattr(rob, n, None))]
    md += section("Resilience layer (`slate_tpu.robust`)",
                  rows_for(rob, rnames))

    import slate_tpu.serve as serve
    vnames = [n for n in getattr(serve, "__all__", dir(serve))
              if not n.startswith("_") and callable(getattr(serve, n, None))]
    md += section("Serving layer (`slate_tpu.serve`)",
                  rows_for(serve, vnames))

    import slate_tpu.obs as obs
    onames = [n for n in getattr(obs, "__all__", dir(obs))
              if not n.startswith("_") and callable(getattr(obs, n, None))]
    md += section("Observability layer (`slate_tpu.obs`)",
                  rows_for(obs, onames))

    import slate_tpu.analysis as analysis
    anames = [n for n in getattr(analysis, "__all__", dir(analysis))
              if not n.startswith("_")
              and callable(getattr(analysis, n, None))]
    md += section("Static analysis (`slate_tpu.analysis`)",
                  rows_for(analysis, anames))

    lp = sorted(n for n in dir(lapack_api)
                if re.fullmatch(r"[sdcz][a-z0-9_]+", n)
                and callable(getattr(lapack_api, n)))
    md += ["## LAPACK skin (`slate_tpu.lapack_api`)", "",
           f"{len(lp)} typed entry points:", "",
           ", ".join(f"`{n}`" for n in lp), ""]

    sk = sorted(n for n in dir(scalapack_api)
                if re.fullmatch(r"p[sdcz][a-z0-9_]+", n)
                and callable(getattr(scalapack_api, n)))
    md += ["## ScaLAPACK skin (`slate_tpu.scalapack_api`)", "",
           f"{len(sk)} distributed `p*` entry points (plus "
           "`gridinit`/`gridexit`):", "",
           ", ".join(f"`{n}`" for n in sk), ""]

    hdr = open(os.path.join(ROOT, "include", "slate_tpu.h")).read()
    cfns = re.findall(r"\b(slate_[a-z0-9_]+)\s*\(", hdr)
    cfns = sorted(set(cfns))
    md += ["## C ABI (`include/slate_tpu.h` + `native/libslate_c_api.so`)", "",
           f"{len(cfns)} exported functions:", "",
           ", ".join(f"`{n}`" for n in cfns), ""]

    f90 = open(os.path.join(ROOT, "tools", "fortran", "slate_tpu.f90")).read()
    ffns = sorted(set(re.findall(
        r"(?:function|subroutine)\s+(slate_[a-z0-9_]+)\s*\(", f90)))
    md += ["## Fortran module (`tools/fortran/slate_tpu.f90`, generated)", "",
           f"{len(ffns)} interfaces:", "",
           ", ".join(f"`{n}`" for n in ffns), ""]

    out = "\n".join(md).rstrip() + "\n"
    path = os.path.join(ROOT, "API_REFERENCE.md")
    with open(path, "w") as f:
        f.write(out)
    print(f"wrote {path}: {len(top)} drivers, {len(pnames)} distributed, "
          f"{len(vnames)} serve, {len(lp)} lapack, {len(sk)} scalapack, "
          f"{len(cfns)} C, {len(ffns)} fortran")


if __name__ == "__main__":
    main()
