"""Guards for the installed jax.

  * a source scan: ``jax.make_mesh`` defaults to ``Explicit`` axis
    types, under which ``with_sharding_constraint`` refuses the specs the
    sharding rules emit ("can only refer to Auto axes of the mesh").  A
    raw ``jax.make_mesh(`` or ``Mesh(`` anywhere outside
    ``repro.common.compat`` fails with the offending file/line;
  * an import sweep: every repro module must import cleanly (an
    import-time failure breaks pytest collection — this pins it to a
    named test instead).
"""
import importlib
import pkgutil
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

RAW_MESH = re.compile(r"jax\.make_mesh\(|\bMesh\(")

SCAN = [ROOT / "chip_smoke.py"] + [
    path for d in ("src", "tests", "benchmarks", "examples", "tools")
    for path in sorted((ROOT / d).rglob("*.py"))]
EXEMPT = {Path("src/repro/common/compat.py"),
          Path("tests/test_compat_guards.py")}


def test_no_raw_pinned_apis_outside_compat():
    offenders = []
    for path in SCAN:
        rel = path.relative_to(ROOT)
        if rel in EXEMPT:
            continue
        for lineno, line in enumerate(path.read_text().splitlines(),
                                      start=1):
            if RAW_MESH.search(line):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "raw mesh construction (Explicit axis types); build meshes via "
        "repro.common.compat.make_mesh/mesh_from_devices:\n"
        + "\n".join(offenders))


def test_every_repro_module_imports_on_pinned_jax():
    import repro

    failures = []
    for mod in pkgutil.walk_packages(repro.__path__, "repro."):
        try:
            importlib.import_module(mod.name)
        except Exception as e:          # noqa: BLE001 - report them all
            failures.append(f"{mod.name}: {type(e).__name__}: {e}")
    assert not failures, "modules failing to import:\n" + "\n".join(failures)
