"""Work a Nerfacto field requires per sample, from the configuration,
counted as `bench/work.py` counts Instant-NGP's: trilinear interpolation
(8 corners x F features, a multiply and an add each, per level) and each
linear (a multiply and an add per weight). The proposal passes between
fields (cumsum, searchsorted, interpolation) are not field operations and
are not counted."""
from __future__ import annotations

from typing import Dict


def _interp(grid: Dict) -> int:
    return grid["n_levels"] * 8 * grid["n_features"] * 2


def proposal_ops_per_sample(model: Dict) -> int:
    """Operations of one proposal field query: its hash grid, then
    (L*F) -> hidden -> 1. The two proposal fields differ only in their
    finest resolution, which costs nothing here."""
    ops = {_interp(p) + 2 * (p["n_levels"] * p["n_features"] * p["hidden_dim"]
                             + p["hidden_dim"]) for p in model["proposals"]}
    if len(ops) != 1:
        raise ValueError("proposal fields of different widths: count them apart")
    return ops.pop()


def shade_ops_per_sample(model: Dict) -> int:
    """Operations of one main field query: hash grid, (L*F) -> hidden ->
    1 + geo, then (SH + geo + appearance) -> color -> color -> 3."""
    f = model["field"]
    h, c, g = f["hidden_dim"], f["color_hidden_dim"], f["geo_feat_dim"]
    sh = (f["sh_degree"] + 1) ** 2
    dims = [(f["n_levels"] * f["n_features"], h), (h, 1 + g),
            (sh + g + model["appearance_dim"], c), (c, c), (c, 3)]
    return _interp(f) + sum(2 * i * o for i, o in dims)
