"""Paper-native configs: the models from the paper's own tables.

vgg11/vgg19 + resnet18 (CIFAR) exercise the 2D-conv layerwise decision
(Tables 3/4/6); beit_large is the convolutional-ViT DP SOTA model of
Table 5 (96.7% on CIFAR-10 at epsilon 1), built by ``models/vit.BEiT``.
"""
from repro.configs.base import ArchConfig

# timm's beit_large_patch16_224: q and v biases, a relative position bias
# table per block, layer scale 1e-5 (the BEiT paper's value for large),
# LayerNorm eps 1e-6, exact GELU
BEIT_LARGE = ArchConfig(
    name="beit-large-patch16",
    family="vit",
    n_layers=24,
    d_model=1024,
    n_heads=16,
    n_kv=16,
    d_ff=4096,
    vocab=0,
    norm="layernorm",
    norm_eps=1e-6,
    act="gelu_erf",
    qkv_bias=True,
    k_bias=False,
    out_bias=True,
    layer_scale=1e-5,
    source="arXiv:2106.08254 (BEiT); paper Table 5",
)
