"""Generate docs/API.md from the package's docstrings.

The reference ships a sphinx-autodoc site (one ``automodule`` stub per
module, /root/reference/docs/source/*.rst + docs.yaml workflow). This image
has no sphinx, so this is a dependency-free equivalent: walk the public
modules, extract signatures + docstrings with ``inspect``, and emit a
single markdown API reference. CI regenerates and fails when the committed
page is stale (``--check``).

Usage:
    python docs/gen_api.py          # (re)write docs/API.md
    python docs/gen_api.py --check  # exit 1 if docs/API.md is stale
"""

from __future__ import annotations

import importlib
import inspect
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# (module, blurb) in reading order — mirrors the reference's doc pages
# (manager/process_group/checkpointing/optim/data/ddp/parameter_server)
# plus the TPU-native additions.
MODULES = [
    ("torchft_tpu.manager", "Per-step fault-tolerance state machine"),
    ("torchft_tpu.boundary", "The commit boundary: refusal rule, "
                             "feature slots"),
    ("torchft_tpu.preemption", "Graceful preemption drain"),
    ("torchft_tpu.exchange", "Cross-group gradient exchange (schedule, "
                             "pack, stage, ring, put)"),
    ("torchft_tpu.communicator", "Resizable cross-group communicators"),
    ("torchft_tpu.backends.host", "Elastic host TCP ring backend"),
    ("torchft_tpu.backends.mesh", "On-device full-membership backend"),
    ("torchft_tpu.transport", "Shared byte-path substrate (pooled "
                              "ranged fetch, async QoS server core)"),
    ("torchft_tpu.checkpointing", "Live peer-to-peer healing transfer"),
    ("torchft_tpu.checkpoint_io", "Durable checkpoint save/load"),
    ("torchft_tpu.ram_ckpt", "RAM checkpoint tier + async demotion"),
    ("torchft_tpu.serving", "Live weight publication + relay fan-out"),
    ("torchft_tpu.tracing", "Per-step tracing + flight recorder"),
    ("torchft_tpu.fleet", "Fleet health plane (straggler/SLO mirror)"),
    ("torchft_tpu.serialization", "Streaming pytree wire format"),
    ("torchft_tpu.optim", "Commit-gated optimizer wrappers"),
    ("torchft_tpu.policy", "Adaptive fault-tolerance policy"),
    ("torchft_tpu.chaos", "Fault injection + churn orchestration"),
    ("torchft_tpu.data", "Replica-group data sharding"),
    ("torchft_tpu.degraded", "Degraded-mode groups (partial chip loss)"),
    ("torchft_tpu.local_sgd", "DiLoCo-style local SGD"),
    ("torchft_tpu.parallel.step", "Fault-tolerant training step"),
    ("torchft_tpu.parallel.mesh", "Device mesh construction"),
    ("torchft_tpu.parallel.sharding", "Parameter/activation sharding rules"),
    ("torchft_tpu.parallel.pipeline", "Pipeline parallelism"),
    ("torchft_tpu.parallel.ring_attention", "Ring attention (sequence "
                                            "parallel)"),
    ("torchft_tpu.ops.flash_attention", "Pallas flash attention kernels"),
    ("torchft_tpu.ops.sparse_index", "A learned sparse attention's indexer: "
     "exact top-k selection by bisection, the indexer's KL loss"),
    ("torchft_tpu.ops.gated_delta", "Gated delta rule: chunked linear-"
                                    "attention scan, causal short conv"),
    ("torchft_tpu.ops.ssd", "Mamba-2 state-space scan: two Pallas kernels "
                            "under one custom_vjp, token-by-token oracle"),
    ("torchft_tpu.models", "Example model zoo"),
    ("torchft_tpu.parameter_server", "Lighthouse-free parameter server"),
    ("torchft_tpu.lighthouse", "Standalone lighthouse CLI"),
    ("torchft_tpu._native", "ctypes bridge to the C++ control plane"),
]


def _clean_doc(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    # Dataclass-style auto-docstrings (e.g. flax modules) embed default
    # reprs with object addresses — scrub them or --check is always stale.
    return re.sub(r" at 0x[0-9a-f]+", "", doc.strip())


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Default-value reprs may embed object addresses (e.g. flax's
    # `_Sentinel object at 0x...`), which would make generation
    # non-deterministic and --check always stale.
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _document_function(name: str, fn, indent: str = "") -> list[str]:
    lines = [f"{indent}#### `{name}{_signature(fn)}`", ""]
    doc = _clean_doc(fn)
    if doc:
        lines += [doc, ""]
    return lines


def _document_class(name: str, cls) -> list[str]:
    lines = [f"### `{name}`", ""]
    bases = [b.__name__ for b in cls.__bases__
             if b.__name__ not in ("object", "Generic")]
    if bases:
        lines += [f"*extends {', '.join(bases)}*", ""]
    doc = _clean_doc(cls)
    if doc:
        lines += [doc, ""]
    if "__init__" in cls.__dict__:
        lines += [f"Constructor: `{name}{_signature(cls.__init__)}`"
                  .replace("(self, ", "(").replace("(self)", "()"), ""]
    for mname, m in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        if isinstance(m, property):
            pdoc = _clean_doc(m) or ""
            lines += [f"#### `{mname}` *(property)*", ""]
            if pdoc:
                lines += [pdoc, ""]
        elif inspect.isfunction(m):
            lines += _document_function(f"{mname}", m)
        elif isinstance(m, (staticmethod, classmethod)):
            lines += _document_function(f"{mname}", m.__func__)
    return lines


def _document_module(modname: str, blurb: str) -> list[str]:
    mod = importlib.import_module(modname)
    lines = [f"## {modname}", "", f"*{blurb}*", ""]
    doc = _clean_doc(mod)
    if doc:
        lines += [doc, ""]
    public = getattr(mod, "__all__", None)
    members = []
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if public is not None and name not in public:
            continue
        if inspect.ismodule(obj):
            continue
        # Only document things defined here (not re-exports), unless the
        # module declares them in __all__.
        defined_here = getattr(obj, "__module__", modname) == modname
        if not defined_here and public is None:
            continue
        members.append((name, obj))
    for name, obj in members:
        if inspect.isclass(obj):
            lines += _document_class(name, obj)
        elif inspect.isfunction(obj):
            lines += _document_function(name, obj)
            lines[-2] = lines[-2].replace("#### ", "### ")  # top-level fn
    return lines


def generate() -> str:
    out = [
        "# torchft_tpu API reference",
        "",
        "*Generated by `python docs/gen_api.py` — do not edit by hand.*",
        "",
        "Package overview and the protocol walkthrough live in"
        " [README.md](../README.md); design rationale per module is in each"
        " module's docstring below.",
        "",
    ]
    out += ["## Contents", ""]
    for modname, blurb in MODULES:
        anchor = modname.replace(".", "")
        out += [f"- [{modname}](#{anchor}) — {blurb}"]
    out += [""]
    for modname, blurb in MODULES:
        out += _document_module(modname, blurb)
    return "\n".join(out).rstrip() + "\n"


def main() -> int:
    target = REPO / "docs" / "API.md"
    content = generate()
    if "--check" in sys.argv:
        if not target.exists() or target.read_text() != content:
            print("docs/API.md is stale: run `python docs/gen_api.py`",
                  file=sys.stderr)
            return 1
        print("docs/API.md is up to date")
        return 0
    target.write_text(content)
    print(f"wrote {target} ({len(content.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
