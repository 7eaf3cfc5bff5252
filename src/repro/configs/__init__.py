"""Paper model specs + benchmark shapes.

Historically this package also carried a 10-architecture LM config zoo,
loaded dynamically via ``importlib``. The zoo was unreachable from the SNN
reproduction path — ``python -m repro.audit`` flagged every module dead —
and has been deleted; tests that still need reduced LM configs hold them
inline (``tests/_smoke_archs.py``). ``get``/``get_smoke`` remain only to
fail loudly with that pointer.
"""
from __future__ import annotations

# the paper's own model zoo (Table 6)
PAPER_SPECS = {
    "mnist": dict(spec="32C3-32C3-P3-10C3-10", hw=28, c=1, params=20568),
    "svhn": dict(spec="1C3-32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-10",
                 hw=32, c=3, params=297990),
    "cifar10": dict(spec="32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-128C3-10",
                    hw=32, c=3, params=446122),
}

# VGG-16 on CIFAR-10, the deep net of the ANN-to-SNN conversion literature
# (Sengupta et al., arXiv:1802.02627): the 13 conv widths of Simonyan and
# Zisserman's configuration D (arXiv:1409.1556, Table 1) at a 32x32x3
# input, with the single 512->10 classifier of the CIFAR-10 VGG16 in
# github.com/kuangliu/pytorch-cifar (models/vgg.py). Batch norm folds into
# the conv weights and biases on conversion. After four 2x2 pools its last
# three 3x3 convs run on a 2x2 map: kernels wider than their map.
VGG16 = dict(
    spec="64C3-64C3-P2-128C3-128C3-P2-256C3-256C3-256C3-P2-512C3-512C3-"
         "512C3-P2-512C3-512C3-512C3-P2-10",
    hw=32, c=3, params=14719818,
    sources=("arXiv:1409.1556 Table 1 column D", "arXiv:1802.02627",
             "github.com/kuangliu/pytorch-cifar models/vgg.py"))

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

_ZOO_REMOVED = (
    "the LM architecture zoo was removed (dead code on the SNN path, "
    "flagged by `python -m repro.audit`); pass an ArchConfig directly — "
    "reduced smoke configs live in tests/_smoke_archs.py"
)


def get(name: str):
    raise LookupError(f"configs.get({name!r}): {_ZOO_REMOVED}")


def get_smoke(name: str):
    raise LookupError(f"configs.get_smoke({name!r}): {_ZOO_REMOVED}")


def shape_applicable(cfg, shape_name: str) -> tuple[bool, str]:
    """Whether a (arch, shape) cell runs; returns (ok, reason-if-skipped)."""
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False, ("pure full-attention arch: 500k dense-KV decode "
                       "skipped per assignment (DESIGN.md long-context policy)")
    return True, ""
