"""The paper's own model: Instant-NGP configs (full + CPU-scale), and
Nerfacto at its published widths.

`paper()` is the Instant-NGP configuration the HERO paper quantizes
(16 hash levels, F=2, T=2^19, two small MLPs; the color MLP takes the 16
spherical-harmonic coefficients of bands 0-3, Instant-NGP Sec. 5.4). It
is trained, searched, compiled and served at these widths on a TPU
(`chip_smoke.py`, the benchmark's `ngp-paper-t19`). `cpu_scale()` is the
reduced-but-same-family config the runnable experiments use on a CPU
(the RL search, baselines, and Table II/III reproductions).

`nerfacto()` is nerfstudio's Nerfacto (`NerfactoModelConfig`, arXiv
2302.04264): `paper()`'s field as the main field, two proposal density
fields and 256 -> 96 -> 48 samples a ray.
"""
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.nerfacto import NerfactoConfig
from repro.nerf.ngp import NGPConfig
from repro.nerf.render import RenderConfig
from repro.nerf.train import TrainConfig


def paper() -> NGPConfig:
    return NGPConfig(
        hash=HashEncodingConfig(
            n_levels=16,
            n_features=2,
            log2_table_size=19,
            base_resolution=16,
            max_resolution=2048,
        ),
        hidden_dim=64,
        geo_feat_dim=15,
        color_hidden_dim=64,
        sh_degree=3,
    )


def nerfacto(n_images: int = 1) -> NerfactoConfig:
    """Nerfacto's published settings: the main field is `paper()`'s (a
    64-wide density MLP with 15 geometry features, a 64-wide 3-layer
    color MLP, 16 SH coefficients) plus a 32-wide appearance embedding
    per training image (`n_images`); two proposal fields of 5 levels,
    F=2, T=2^17, resolutions 16..128 and 16..256, with 16-wide MLPs;
    near 0.05, far 1000."""
    prop = dict(n_levels=5, n_features=2, log2_table_size=17,
                base_resolution=16)
    return NerfactoConfig(
        field=paper(),
        proposals=(HashEncodingConfig(max_resolution=128, **prop),
                   HashEncodingConfig(max_resolution=256, **prop)),
        proposal_hidden=16, appearance_dim=32, n_images=n_images,
        n_initial=256, n_resampled=(96, 48), near=0.05, far=1000.0,
        histogram_padding=0.01,
    )


def cpu_scale() -> NGPConfig:
    return NGPConfig(
        hash=HashEncodingConfig(
            n_levels=8,
            n_features=2,
            log2_table_size=11,
            base_resolution=4,
            max_resolution=64,
        ),
        hidden_dim=32,
        geo_feat_dim=15,
        color_hidden_dim=32,
        sh_degree=3,
    )


def cpu_render() -> RenderConfig:
    return RenderConfig(n_samples=32)


def cpu_train() -> TrainConfig:
    return TrainConfig(steps=300, batch_rays=512, lr=5e-3)
