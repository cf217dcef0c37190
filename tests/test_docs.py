"""Docs quality gates: relative links in README/docs must resolve, and the
device-sampling-pipeline modules must keep full public-API docstring
coverage (the PR-1 additions originally shipped thin — this stops that from
regressing)."""

import importlib
import inspect
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_readme_and_docs_exist():
    assert (ROOT / "README.md").exists()
    assert (ROOT / "docs" / "architecture.md").exists()
    assert (ROOT / "docs" / "kernels.md").exists()
    assert (ROOT / "docs" / "dtdg.md").exists()
    assert (ROOT / "docs" / "experiment.md").exists()
    assert (ROOT / "docs" / "sharding.md").exists()
    assert (ROOT / "docs" / "serving.md").exists()
    assert (ROOT / "docs" / "storage.md").exists()
    assert (ROOT / "docs" / "observability.md").exists()
    assert (ROOT / "docs" / "benchmarks.md").exists()


def test_relative_doc_links_resolve():
    """Same rule as the CI link-check step (scripts/check_doc_links.py)."""
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        from check_doc_links import broken_links, doc_files
    finally:
        sys.path.pop(0)
    assert len(doc_files(ROOT)) >= 3
    assert broken_links(ROOT) == []


# Modules whose public surface must stay documented (the device-resident
# sampling pipeline: PR-1 additions + the fused-attention layer + the
# scan-compiled DTDG pipeline + the mesh-sharded sampler layer).
DOCUMENTED_MODULES = [
    "repro.core.device_sampler",
    "repro.core.device_uniform",
    "repro.distributed.sharding",
    "repro.core.discretize",
    "repro.core.graph",
    "repro.core.loader",
    "repro.core.negatives",
    "repro.core.tg_hooks",
    "repro.core.sampler",
    "repro.core.recipes",
    "repro.kernels.temporal_attention.kernel",
    "repro.kernels.temporal_attention.ops",
    "repro.kernels.temporal_attention.ref",
    "repro.nn.attention",
    "repro.nn.graph_conv",
    "repro.models.tg.common",
    "repro.models.tg.snapshot",
    "repro.train.tg_trainer",
    "repro.train.loop",
    "repro.train.nodeprop",
    "repro.tg.specs",
    "repro.tg.experiment",
    "repro.serve.graph_service",
    "repro.serve.faults",
    "repro.storage.base",
    "repro.storage.memory",
    "repro.storage.mmap",
    "repro.storage.csr",
    "repro.storage.windows",
    "repro.obs.records",
    "repro.obs.sinks",
    "repro.obs.telemetry",
    "repro.obs.profiler",
    # Test infrastructure is public surface too: the shared kernel-parity
    # harness and the jaxpr-inspection helpers are how new kernel families
    # get their acceptance coverage.
    "tests.utils",
    "tests.kernels.harness",
]


def _undocumented(module_name):
    m = importlib.import_module(module_name)
    missing = []
    if not inspect.getdoc(m):
        missing.append(module_name)
    for name, obj in vars(m).items():
        if name.startswith("_") or getattr(obj, "__module__", None) != module_name:
            continue
        if inspect.isfunction(obj):
            if not inspect.getdoc(obj):
                missing.append(f"{module_name}.{name}")
        elif inspect.isclass(obj):
            if not inspect.getdoc(obj):
                missing.append(f"{module_name}.{name}")
            for mname, meth in vars(obj).items():
                if mname.startswith("_"):
                    continue
                if inspect.isfunction(meth) and not inspect.getdoc(meth):
                    missing.append(f"{module_name}.{name}.{mname}")
    return missing


def test_public_api_docstrings():
    missing = []
    for mod in DOCUMENTED_MODULES:
        missing += _undocumented(mod)
    assert missing == [], f"undocumented public symbols: {missing}"
