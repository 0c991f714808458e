"""QuantArtifact: the deployable output of a HERO search.

A search run used to end in a frontier JSON — a dict of bit vectors. The
artifact closes the loop to deployment: `compile_artifact(env, bits)`
QAT-finetunes the pretrained weights under the policy, quantizes them to
the packed integer inference form (`FusedPack`), and bundles everything a
render service needs to serve the scene without the training stack:

  - the finetuned float parameters (reference mode / re-packing);
  - the policy bits + calibration ranges (the quant spec is re-derived
    deterministically on load — one source of truth);
  - the packed `FusedPack`: SUB-BYTE weight code words and integer
    hash-table code words (`repro.quant.packing.PackedTensor` bit-plane
    layout) + scales (loaded verbatim, not rebuilt: the bundle IS the
    deploy format, and a 4-bit policy ships 4-bit payloads);
  - the baked occupancy grid (empty-space culling at serve time), for
    an Instant-NGP field; a Nerfacto field (`NerfactoConfig`) has none:
    its proposal fields place the samples, and its pack is a
    `NerfactoPack` of three packed fields;
  - hardware-target metadata + latency/model-size/PSNR at compile, with
    `model_bytes` MEASURED from the stored payload bytes — by the shared
    size function, exactly the frontier's model_bytes for the policy.

`save`/`load` use one directory: `arrays.npz` + `manifest.json` with
per-array sha256 and a schema version — corrupt or truncated bundles fail
loudly, the same auditability contract as `repro.checkpoint`.

Schema v2 stores packed words (`...::pt::words/scale/offset` triplets
described by the manifest's `packed_tensors` map). A v1 directory (int8
weight codes + float-carrier hash tables) still loads: integrity checks
run against ITS manifest first, then the pack is rebuilt from the
finetuned params + policy bits through the same deterministic
`build_fused_pack` path — the in-memory object is a full v2 artifact
(saving it writes v2) and serves at the PSNR a v2 compile of the same
params produces.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.kernels.repack import unrepack_planar
from repro.nerf import nerfacto
from repro.nerf.fast_render import (
    FastRenderEngine,
    FusedPack,
    NerfactoPack,
    build_fused_pack,
    fused_pack_stored_bytes,
    repack_fused_pack,
)
from repro.nerf.hash_encoding import HashEncodingConfig
from repro.nerf.ngp import (
    NGPConfig,
    NGPQuantSpec,
    make_quant_units,
    spec_from_policy,
)
from repro.nerf.occupancy import OccupancyGrid, bake_occupancy_cached
from repro.nerf.render import RenderConfig
from repro.quant.packing import PackedTensor
from repro.quant.policy import QuantPolicy

SCHEMA_VERSION = 2
# npz key separator: parameter names themselves contain "/" ("sigma/0"),
# so nesting is encoded with a separator that cannot appear in names.
_SEP = "::"


@dataclasses.dataclass
class QuantArtifact:
    """Serialized deployable bundle for one (scene, policy) pair."""

    scene: str
    bits: List[int]
    cfg: Union[NGPConfig, nerfacto.NerfactoConfig]
    rcfg: Optional[RenderConfig]  # None for Nerfacto (the config samples)
    # Full SceneConfig (as a dict) of the dataset the compile metrics were
    # measured on — a consumer can rebuild the EXACT eval set (parity
    # comparisons against `metrics["psnr"]` are meaningless on any other).
    scene_cfg: Dict
    params: Dict  # finetuned float weights, {top: {sub: array}}
    act_ranges: jnp.ndarray  # (n_linear, 2) calibrated activation ranges
    pack: Union[FusedPack, NerfactoPack]  # packed integer inference form
    occ: Optional[OccupancyGrid]  # None for Nerfacto
    hardware: Dict  # HardwareTarget.describe() of the search target
    metrics: Dict  # psnr / latency_cycles / model_bytes / fqr at compile
    schema_version: int = SCHEMA_VERSION

    # ------------------------------------------------------------------
    @property
    def proposal_sampled(self) -> bool:
        """A Nerfacto field: fixed samples a ray placed by its proposal
        fields, no occupancy grid, no sample budget."""
        return isinstance(self.cfg, nerfacto.NerfactoConfig)

    def spec(self):
        """Quant spec re-derived from (bits, act_ranges) — identical to the
        one the compile step used (same `spec_from_policy` path)."""
        if self.proposal_sampled:
            units, to_spec = nerfacto.make_quant_units, nerfacto.spec_from_policy
        else:
            units, to_spec = make_quant_units, spec_from_policy
        policy = QuantPolicy.uniform(units(self.cfg), 8).with_bits(
            list(self.bits))
        return to_spec(self.cfg, policy, self.act_ranges)

    def engine(self, **kw) -> FastRenderEngine:
        """Fused render engine over the LOADED pack (codes are served
        verbatim, not re-quantized)."""
        kw.setdefault("mode", "fused")
        return FastRenderEngine(
            self.params, self.cfg, self.rcfg, spec=self.spec(), occ=self.occ,
            pack=self.pack, **kw,
        )

    def stored_model_bytes(self) -> int:
        """Exact bytes of the quantized model payload as stored on disk
        (packed weight/table words + any f32 carriers) — the number
        `metrics["model_bytes"]` records and the frontier's shared size
        function predicts."""
        return sum(fused_pack_stored_bytes(p) for _, p in self.pack.fields())

    def resident_bytes(self) -> int:
        """Total in-memory bytes of everything the artifact keeps resident
        (float params + packed codes + occupancy + calibration) — the
        price the serve engine's LRU cache charges for keeping the scene
        loaded. Metadata reads only (`.nbytes` per array), no host copies:
        cheap enough to call on every admission decision."""

        def nb(v) -> int:
            if isinstance(v, PackedTensor):
                return int(v.words.nbytes + v.scale.nbytes + v.offset.nbytes)
            return int(v.nbytes)

        total = nb(self.act_ranges)
        if self.occ is not None:
            total += nb(self.occ.occ)
        if self.proposal_sampled:
            total += nb(self.pack.appearance)
        for sub in self.params.values():
            total += sum(nb(v) for v in sub.values())
        for _, pack in self.pack.fields():
            for lyr in pack.layers.values():
                total += sum(nb(v) for v in lyr.values())
            total += sum(nb(t) for t in pack.hash_tables.values())
            # Staged compute-layout forms (tile-native words, concatenated
            # dequantized tables, f32 carriers) are resident too — the
            # cache charges for the speed; stored bytes don't change.
            total += sum(nb(v) for v in pack.compute.values())
        return total

    def cache_key(self) -> str:
        """Cheap stable identity for serve-engine cache keys and logs:
        (scene, hardware, policy bits). Not an integrity check — the
        manifest sha256s own that."""
        hw = (
            self.hardware.get("name", "?")
            if isinstance(self.hardware, dict) else str(self.hardware)
        )
        return f"{self.scene}/{hw}/b" + "".join(str(int(b)) for b in self.bits)

    # ------------------------------------------------------------------
    # Serialization
    # ------------------------------------------------------------------
    def _arrays(self) -> Tuple[Dict[str, np.ndarray], Dict[str, Dict]]:
        """-> (flat array dict, packed-tensor static metadata by prefix).

        A `PackedTensor` value at logical key K becomes three arrays
        (K::pt::words / K::pt::scale / K::pt::offset); its static (bits,
        shape) ride in the manifest's `packed_tensors[K]`. A proposal
        field's layers and tables carry its name after the `pack` /
        `packtab` root (`pack::prop1::prop1/0::wq`)."""
        out: Dict[str, np.ndarray] = {"act_ranges": np.asarray(self.act_ranges)}
        packed: Dict[str, Dict] = {}

        def emit(key, v):
            if isinstance(v, PackedTensor):
                # Disk ALWAYS holds the storage codec's planar word order
                # (schema v2, byte-identical regardless of any runtime
                # tile repack): `unrepack_planar` is the exact inverse
                # permutation and a no-op for planar tensors.
                v = unrepack_planar(v)
                out[f"{key}{_SEP}pt{_SEP}words"] = np.asarray(v.words)
                out[f"{key}{_SEP}pt{_SEP}scale"] = np.asarray(v.scale)
                out[f"{key}{_SEP}pt{_SEP}offset"] = np.asarray(v.offset)
                packed[key] = {
                    "bits": int(v.bits),
                    "shape": [int(s) for s in v.shape],
                    "layout": "planar",
                }
            else:
                out[key] = np.asarray(v)

        for top, sub in self.params.items():
            for k, v in sub.items():
                out[f"params{_SEP}{top}{_SEP}{k}"] = np.asarray(v)
        for field, pack in self.pack.fields():
            root = f"{_SEP}{field}" if field else ""
            for name, lyr in pack.layers.items():
                for k, v in lyr.items():
                    emit(f"pack{root}{_SEP}{name}{_SEP}{k}", v)
            for name, t in pack.hash_tables.items():
                emit(f"packtab{root}{_SEP}{name}", t)
        if self.proposal_sampled:
            out["appearance"] = np.asarray(self.pack.appearance)
        if self.occ is not None:
            out["occ"] = np.asarray(self.occ.occ)
        return out, packed

    def save(self, path) -> Path:
        """Write the bundle to directory `path` (npz first, manifest last,
        both via tmp + rename so a crash never leaves a loadable lie)."""
        path = Path(path)
        path.mkdir(parents=True, exist_ok=True)
        arrays, packed_meta = self._arrays()
        manifest = {
            "schema_version": SCHEMA_VERSION,
            "model": "nerfacto" if self.proposal_sampled else "ngp",
            "packed_tensors": packed_meta,
            "scene": self.scene,
            "bits": [int(b) for b in self.bits],
            "cfg": dataclasses.asdict(self.cfg),
            "rcfg": (None if self.rcfg is None
                     else dataclasses.asdict(self.rcfg)),
            "scene_cfg": self.scene_cfg,
            "pack_modes": (
                {f: list(p.modes) for f, p in self.pack.fields()}
                if self.proposal_sampled else list(self.pack.modes)
            ),
            "occ": None if self.occ is None else {
                "resolution": self.occ.resolution,
                "threshold": self.occ.threshold,
                "occupied_fraction": self.occ.occupied_fraction,
            },
            "hardware": self.hardware,
            "metrics": self.metrics,
            "arrays": {
                k: {
                    "shape": list(v.shape),
                    "dtype": str(v.dtype),
                    "sha256": _sha(v),
                }
                for k, v in arrays.items()
            },
        }
        tmp_npz = path / "arrays.npz.tmp"
        with open(tmp_npz, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp_npz, path / "arrays.npz")
        tmp_manifest = path / "manifest.json.tmp"
        tmp_manifest.write_text(json.dumps(manifest, indent=2))
        os.replace(tmp_manifest, path / "manifest.json")
        return path

    @staticmethod
    def load(path) -> "QuantArtifact":
        """Load a saved bundle. Integrity (array-set match + per-array
        sha256 against the directory's OWN manifest) is verified for every
        schema version before any reconstruction; a v1 directory is then
        auto-upgraded in memory (module docstring).

        After verification each field's compute forms are staged (the
        one-time tile-native permutation + fused-encode staging of
        `repack_fused_pack`, at its default tile), as a compile
        stages them; the stored bytes are untouched."""
        path = Path(path)
        manifest = json.loads((path / "manifest.json").read_text())
        version = int(manifest.get("schema_version", -1))
        if version > SCHEMA_VERSION or version < 1:
            raise ValueError(
                f"artifact {path} has schema_version={version}; this build "
                f"reads <= {SCHEMA_VERSION}"
            )
        with np.load(path / "arrays.npz") as z:
            arrays = {k: z[k] for k in z.files}

        want = manifest["arrays"]
        if set(want) != set(arrays):
            raise ValueError(
                f"artifact {path}: manifest/npz array sets differ "
                f"(missing {sorted(set(want) - set(arrays))}, "
                f"unexpected {sorted(set(arrays) - set(want))})"
            )
        for k, meta in want.items():
            if _sha(arrays[k]) != meta["sha256"]:
                raise ValueError(f"artifact {path}: array {k!r} failed its "
                                 "sha256 integrity check")

        model = manifest.get("model", "ngp")
        cfg = _config_from_dict(model, manifest["cfg"])
        rcfg = (None if manifest["rcfg"] is None
                else RenderConfig(**manifest["rcfg"]))

        packed_meta = manifest.get("packed_tensors", {})

        def take_packed(prefix: str) -> PackedTensor:
            meta = packed_meta[prefix]
            return PackedTensor(
                words=jnp.asarray(arrays[f"{prefix}{_SEP}pt{_SEP}words"]),
                scale=jnp.asarray(arrays[f"{prefix}{_SEP}pt{_SEP}scale"]),
                offset=jnp.asarray(arrays[f"{prefix}{_SEP}pt{_SEP}offset"]),
                bits=int(meta["bits"]),
                shape=tuple(int(s) for s in meta["shape"]),
                layout=str(meta.get("layout", "planar")),
            )

        params: Dict[str, Dict] = {}
        # field name ("" = the main field) -> its layers / tables
        layers: Dict[str, Dict[str, Dict]] = {}
        tables: Dict[str, Dict[str, jnp.ndarray]] = {}

        def place(parts, value):
            # pack[::field]::layer::key and packtab[::field]::table
            rest = parts[1:]
            depth = 2 if parts[0] == "pack" else 1
            field = rest.pop(0) if len(rest) > depth else ""
            if parts[0] == "pack":
                layers.setdefault(field, {}).setdefault(rest[0], {})[
                    rest[1]] = value
            else:
                tables.setdefault(field, {})[rest[0]] = value

        for k, v in arrays.items():
            parts = k.split(_SEP)
            if len(parts) >= 2 and parts[-2] == "pt":
                continue  # component of a PackedTensor, handled below
            if parts[0] == "params":
                params.setdefault(parts[1], {})[parts[2]] = jnp.asarray(v)
            elif parts[0] in ("pack", "packtab"):
                place(parts, jnp.asarray(v))
        for prefix in packed_meta:
            parts = prefix.split(_SEP)
            if parts[0] in ("pack", "packtab"):
                place(parts, take_packed(prefix))

        occ_meta = manifest["occ"]
        occ = None if occ_meta is None else OccupancyGrid(
            occ=jnp.asarray(arrays["occ"]),
            resolution=int(occ_meta["resolution"]),
            threshold=float(occ_meta["threshold"]),
            occupied_fraction=float(occ_meta["occupied_fraction"]),
        )
        bits = [int(b) for b in manifest["bits"]]
        act_ranges = jnp.asarray(arrays["act_ranges"])
        metrics = dict(manifest["metrics"])

        if version == 1:
            # v1 auto-upgrade: the stored pack is the legacy int8/f32
            # form (int8 w_codes + f32 w_deq + float-carrier tables).
            # Re-pack from the verified finetuned params through the SAME
            # deterministic build path a v2 compile uses — identical
            # codes, identical served PSNR — and re-measure model_bytes
            # from what v2 actually stores.
            units = make_quant_units(cfg)
            policy = QuantPolicy.uniform(units, 8).with_bits(bits)
            spec = spec_from_policy(cfg, policy, act_ranges)
            pack = build_fused_pack(params, cfg, spec)
            metrics["model_bytes"] = float(fused_pack_stored_bytes(pack))
        else:
            modes = manifest["pack_modes"]
            if model != "nerfacto":
                modes = {"": modes}
                hcfgs = {"": cfg.hash}
            else:
                hcfgs = {"": cfg.field.hash, **{
                    f"prop{k + 1}": h for k, h in enumerate(cfg.proposals)}}
            fields = {}
            for field, m in modes.items():
                fields[field] = repack_fused_pack(FusedPack(
                    layers=layers[field], hash_tables=tables[field],
                    modes=tuple(m), hash_cfg=hcfgs[field],
                ))
            pack = fields.pop("")
            if model == "nerfacto":
                pack = NerfactoPack(
                    main=pack,
                    proposals=tuple(fields[f"prop{k + 1}"]
                                    for k in range(len(fields))),
                    appearance=jnp.asarray(arrays["appearance"]),
                )

        return QuantArtifact(
            scene=manifest["scene"],
            bits=bits,
            cfg=cfg,
            rcfg=rcfg,
            scene_cfg=dict(manifest["scene_cfg"]),
            params=params,
            act_ranges=act_ranges,
            pack=pack,
            occ=occ,
            hardware=manifest["hardware"],
            metrics=metrics,
            schema_version=SCHEMA_VERSION,
        )


def _ngp_config(d: Dict) -> NGPConfig:
    d = dict(d)
    return NGPConfig(hash=HashEncodingConfig(**d.pop("hash")), **d)


def _config_from_dict(model: str, d: Dict):
    """The manifest's `cfg` back into the model's frozen config."""
    if model == "ngp":
        return _ngp_config(d)
    if model != "nerfacto":
        raise ValueError(f"unknown model {model!r} in artifact manifest")
    d = dict(d)
    return nerfacto.NerfactoConfig(
        field=_ngp_config(d.pop("field")),
        proposals=tuple(HashEncodingConfig(**h) for h in d.pop("proposals")),
        n_resampled=tuple(d.pop("n_resampled")), **d,
    )


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Compile: (env, policy bits) -> QuantArtifact
# ---------------------------------------------------------------------------
def compile_artifact(
    env,  # NGPQuantEnv (typed loosely to avoid an import cycle)
    bits: Optional[Sequence[int]] = None,
    finetune_steps: Optional[int] = None,
) -> QuantArtifact:
    """Lower a searched policy to a deployable bundle.

    Runs the same QAT finetune + fused PSNR evaluation the env's episode
    path uses, simulates the policy on the env's hardware target, packs
    the finetuned weights to integer inference form, and bundles the
    occupancy grid. `bits=None` compiles the uniform 8-bit policy.
    """
    from repro.nerf.train import finetune_ngp

    if bits is None:
        bits = [8] * env.n_units
    bits = [int(b) for b in bits]
    steps = env.ecfg.finetune_steps if finetune_steps is None else finetune_steps

    policy = QuantPolicy.uniform(env.units, 8).with_bits(bits)
    spec = spec_from_policy(env.cfg, policy, env.act_ranges)
    ft_params, _ = finetune_ngp(
        dict(env.params), env.dataset, env.cfg, env.rcfg, env.tcfg, spec, steps
    )
    psnr = env.eval_psnr(ft_params, spec)
    lat = env.simulate_policy(policy)
    occ = env.occ
    if occ is None:  # reference-backend env: bake for the fused artifact
        occ = bake_occupancy_cached(
            env.params, env.cfg, resolution=env.ecfg.occ_resolution,
            threshold=env.ecfg.occ_threshold,
        )
    pack = build_fused_pack(ft_params, env.cfg, spec)
    # MEASURED payload bytes. The simulator's model_bytes goes through the
    # same shared size function (`repro.quant.packing`), so the two are
    # equal — pinned by tests — but the artifact records what it stores.
    model_bytes = fused_pack_stored_bytes(pack)
    return QuantArtifact(
        scene=env.scene_name,
        bits=bits,
        cfg=env.cfg,
        rcfg=env.rcfg,
        scene_cfg=dataclasses.asdict(env.dataset.cfg),
        params=ft_params,
        act_ranges=env.act_ranges,
        pack=pack,
        occ=occ,
        hardware=env.target.describe(),
        metrics={
            "psnr": float(psnr),
            "latency_cycles": float(lat.total_cycles),
            "model_bytes": float(model_bytes),
            "fqr": float(policy.fqr()),
            "finetune_steps": int(steps),
        },
    )
