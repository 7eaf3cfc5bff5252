"""The nets this repo serves: the paper's three (Table 6) and VGG-16."""
from __future__ import annotations

# the paper's three nets (Table 6)
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
