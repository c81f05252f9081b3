"""Torch-checkpoint -> flax-pytree weight conversion.

The reference ships ``.pth`` model-zoo checkpoints saved from an
``nn.DataParallel`` wrapper (``module.``-prefixed keys, train.py:187,212).
This maps them onto :class:`raft_tpu.models.raft.RAFT` variables:

- ``module.`` prefix stripped (SURVEY.md §3.5);
- conv weights OIHW -> HWIO;
- ``fnet/cnet`` residual stages ``layerX.Y.`` -> ``layerX_Y``; the
  downsample Sequential's conv (``downsample.0``) -> ``downsample_conv``,
  and its norm alias (``downsample.1``, the same tensor the reference also
  registers as ``norm3``/``norm4``, extractor.py:41-46) is dropped;
- ``update_block.`` -> the scan-carried ``refine/update_block``;
- the mask-head Sequential ``mask.0``/``mask.2`` (update.py:122-125)
  -> ``upsampler/mask_head/mask_conv1|2`` (the mask head is hoisted out
  of the refinement scan into the upsample stage, models/raft.py);
- norm ``weight/bias`` -> ``scale/bias`` under the auto-named
  ``BatchNorm_0``/``GroupNorm_0`` submodule, ``running_mean/var`` -> the
  ``batch_stats`` collection; ``num_batches_tracked`` is dropped;
- the GRU's separate z/r gate convs (``convz*``/``convr*``) are merged
  into our fused double-width ``convzr*`` tensors (output-axis concat,
  z first — see update.py ConvGRU/SepConvGRU);
- the public GMA state dict (github.com/zacjiang/GMA ``RAFTGMA``):
  ``att.to_qk`` -> ``att/to_qk``, ``update_block.aggregator.to_v|gamma``
  -> ``refine/update_block/aggregator``; ``att.pos_emb.*`` (the
  relative-position tables every GMA checkpoint carries and the
  published content-only model never reads) is dropped.  A GMA state
  dict and a RAFT template, or the reverse, is refused by name
  (:func:`_check_arch`);
- the public SEA-RAFT state dict (github.com/princeton-vl/SEA-RAFT
  ``RAFT``): in both ResNet trunks ``bnN`` -> ``normN`` and
  ``final_conv`` -> ``conv2``; ``init_conv`` as it is; the Sequentials
  ``flow_head.0|2`` -> ``flow_head/conv1|2`` and ``upsample_weight.0|2``
  -> ``upsampler/mask_head/mask_conv1|2``; ``update_block.refine.N`` ->
  ``refine/update_block/refine_N`` with the depthwise kernel
  ``(C, 1, 7, 7)`` -> ``(7, 7, 1, C)``, the LayerNorm's ``weight`` ->
  ``scale`` and the two ``nn.Linear`` weights ``(out, in)`` -> 1x1
  kernels ``(1, 1, in, out)``.  Such a state dict and a template of
  another architecture, or the reverse, is refused naming the first key
  that has no place.

Conversion is validated structurally: every template leaf must be written
exactly once with a matching shape, and every torch tensor consumed.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Tuple

import numpy as np

from raft_tpu.cli import add_arch_argument, arch_from_args
from raft_tpu.config import RAFTConfig


def _flatten(tree, prefix=()) -> Dict[Tuple[str, ...], Any]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict) or hasattr(v, "items"):
            out.update(_flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def _unflatten(flat: Dict[Tuple[str, ...], Any]):
    tree: Dict[str, Any] = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def _torch_key_to_path(key: str):
    """Reference state-dict key -> (collection, flax path tuple) or None to
    skip (aliases / counters)."""
    key = re.sub(r"^module\.", "", key)
    parts = key.split(".")

    if parts[-1] == "num_batches_tracked":
        return None
    if parts[:2] == ["att", "pos_emb"]:
        return None
    if "downsample" in parts:
        # downsample.0 = conv; downsample.1 aliases norm3/norm4 (which have
        # their own keys).
        i = parts.index("downsample")
        if parts[i + 1] == "1":
            return None
        parts = parts[:i] + ["downsample_conv"] + parts[i + 2:]

    # layerX.Y -> layerX_Y; GMFlow's layers.N -> layers_N, mlp.N -> mlp_N
    merged = []
    for p in parts:
        if merged and re.fullmatch(r"layer\d+|layers|mlp", merged[-1]) \
                and re.fullmatch(r"\d+", p):
            merged[-1] = f"{merged[-1]}_{p}"
        else:
            merged.append(p)
    parts = merged

    # mask Sequential -> the hoisted upsample-stage mask head
    if "mask" in parts:
        i = parts.index("mask")
        conv = {"0": "mask_conv1", "2": "mask_conv2"}[parts[i + 1]]
        parts = ["upsampler", "mask_head", conv] + parts[i + 2:]

    # SEA-RAFT: the two heads' Sequentials, the trunks' names, the blocks
    if parts[0] == "flow_head" and parts[1] in ("0", "2"):
        parts = ["flow_head", {"0": "conv1", "2": "conv2"}[parts[1]]] \
            + parts[2:]
    if parts[0] == "upsampler" and parts[1] in ("0", "2"):     # GMFlow's
        parts = ["upsampler", {"0": "conv1", "2": "conv2"}[parts[1]]] \
            + parts[2:]
    if parts[0] == "upsample_weight":
        parts = ["upsampler", "mask_head",
                 {"0": "mask_conv1", "2": "mask_conv2"}[parts[1]]] + parts[2:]
    if parts[0] in ("fnet", "cnet"):
        parts = [re.sub(r"^bn(\d)$", r"norm\1", p) for p in parts]
        parts = ["conv2" if p == "final_conv" else p for p in parts]
    if parts[:2] == ["update_block", "refine"]:
        parts = ["update_block", f"refine_{parts[2]}"] + parts[3:]

    if parts[0] == "update_block":
        parts = ["refine"] + parts

    leaf = parts[-1]
    if leaf in ("running_mean", "running_var"):
        stat = "mean" if leaf == "running_mean" else "var"
        return "batch_stats", tuple(parts[:-1]) + ("<norm>", stat)
    if leaf == "weight":
        return "params", tuple(parts[:-1]) + ("<weight>",)
    if leaf == "bias":
        return "params", tuple(parts[:-1]) + ("<bias>",)
    if leaf == "gamma":
        return "params", tuple(parts)
    raise ValueError(f"unrecognized torch key: {key}")


def _to_np(t) -> np.ndarray:
    """torch tensor or ndarray -> ndarray."""
    return np.asarray(getattr(t, "numpy", lambda: t)())


def _fuse_gru_zr(state_dict: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the reference GRU's separate z/r gate convs into the fused
    double-width ``convzr*`` tensors our model uses (update.py: ConvGRU /
    SepConvGRU fuse the two same-input convs; concat on the output
    axis — axis 0 of OIHW weights and of biases)."""
    out = dict(state_dict)
    for key in list(state_dict):
        m = re.fullmatch(r"(.*\.gru\.)convz(\d*)\.(weight|bias)", key)
        if not m:
            continue
        prefix, idx, leaf = m.groups()
        rkey = f"{prefix}convr{idx}.{leaf}"
        out[f"{prefix}convzr{idx}.{leaf}"] = np.concatenate(
            [_to_np(state_dict[key]), _to_np(state_dict[rkey])], axis=0)
        del out[key], out[rkey]
    return out


_GMA_KEY = re.compile(r"^(module\.)?(att\.|update_block\.aggregator\.)")
_SEA_KEY = re.compile(r"^(module\.)?(init_conv\.|flow_head\.|"
                      r"upsample_weight\.|update_block\.refine\.)")
_GMFLOW_KEY = re.compile(r"^(module\.)?(backbone\.|transformer\.|"
                         r"feature_flow_attn\.|upsampler\.)")
# BasicEncoder's bias leaves that GMFlow's bias-free convolutions leave
# unfilled: the stem's and every 3x3's, each in front of an instance norm
_GMFLOW_INERT_BIAS = re.compile(
    r"^params/backbone/(conv1|layer\d_\d/conv[12])/bias$")


def _check_arch(state_dict, template) -> None:
    """A GMA state dict fills only a 'gma' template and a RAFT one only a
    RAFT template: say which keys stand in the way, before the first
    shape mismatch (the GRU's input width) says something less useful."""
    gma_keys = sorted(k for k in state_dict if _GMA_KEY.match(k)
                      and ".pos_emb." not in k)
    wants = "att" in template["params"]
    if gma_keys and not wants:
        raise ValueError(
            "the state dict is a GMA checkpoint (it holds "
            + ", ".join(gma_keys) + ") and the model is not: the "
            "template has no att/* or refine/update_block/aggregator/* "
            "leaves; convert with --arch gma")
    if wants and not gma_keys:
        raise ValueError(
            "the model is GMA and the state dict holds none of its "
            "att.* / update_block.aggregator.* keys (att.to_qk.weight, "
            "update_block.aggregator.to_v.weight, "
            "update_block.aggregator.gamma): not a GMA checkpoint")
    sea_keys = sorted(k for k in state_dict if _SEA_KEY.match(k))
    wants = "init_conv" in template["params"]
    if sea_keys and not wants:
        raise ValueError(
            f"the state dict is a SEA-RAFT checkpoint ({sea_keys[0]!r} is "
            f"the first of {len(sea_keys)} keys that have no place) and "
            "the model is not: the template has no init_conv/*, "
            "flow_head/* or refine/update_block/refine_N/* leaves; "
            "convert with --arch searaft")
    if wants and not sea_keys:
        raise ValueError(
            "the model is SEA-RAFT and the state dict is not: "
            "'init_conv.weight' is the first key missing (then "
            "flow_head.0.weight, update_block.refine.0.dwconv.weight, "
            "upsample_weight.0.weight)")
    gmf_keys = sorted(k for k in state_dict if _GMFLOW_KEY.match(k))
    wants = "transformer" in template["params"]
    if gmf_keys and not wants:
        raise ValueError(
            f"the state dict is a GMFlow checkpoint ({gmf_keys[0]!r} is "
            f"the first of {len(gmf_keys)} keys that have no place) and "
            "the model is not: the template has no backbone/*, "
            "transformer/* or feature_flow_attn/* leaves; convert with "
            "--arch gmflow")
    if wants and not gmf_keys:
        raise ValueError(
            "the model is GMFlow and the state dict is not: "
            "'backbone.conv1.weight' is the first key missing (then "
            "transformer.layers.0.self_attn.q_proj.weight, "
            "feature_flow_attn.q_proj.weight, upsampler.0.weight)")


def convert_state_dict(state_dict: Dict[str, Any],
                       template: Dict[str, Any]) -> Dict[str, Any]:
    """Map a reference torch ``state_dict`` (tensors or ndarrays) onto the
    flax ``template`` variables ({'params': ..., 'batch_stats': ...})."""
    _check_arch(state_dict, template)
    state_dict = _fuse_gru_zr(state_dict)
    flat_tmpl = {("params",) + p: v
                 for p, v in _flatten(template["params"]).items()}
    flat_tmpl.update(
        {("batch_stats",) + p: v
         for p, v in _flatten(template.get("batch_stats", {})).items()})

    out: Dict[Tuple[str, ...], np.ndarray] = {}
    for key, tensor in state_dict.items():
        mapped = _torch_key_to_path(key)
        if mapped is None:
            continue
        coll, path = mapped
        arr = _to_np(tensor)

        # Resolve the placeholder leaf against the template: norm
        # weight/bias live under an auto-named BatchNorm_0/GroupNorm_0
        # submodule; conv weight/bias live directly under the conv module.
        prefix = (coll,) + path[:-1]
        leaf = path[-1]
        if leaf == "gamma":
            candidates = [(coll,) + path]
        elif leaf == "<weight>":
            candidates = [prefix + ("kernel",),
                          prefix + ("BatchNorm_0", "scale"),
                          prefix + ("GroupNorm_0", "scale"),
                          prefix + ("scale",)]      # a LayerNorm's own
        elif leaf == "<bias>":
            candidates = [prefix + ("bias",),
                          prefix + ("BatchNorm_0", "bias"),
                          prefix + ("GroupNorm_0", "bias")]
        else:  # mean / var (path = (..., '<norm>', stat))
            base = (coll,) + path[:-2]
            candidates = [base + ("BatchNorm_0", leaf)]
        full = next((c for c in candidates if c in flat_tmpl), None)
        if full is None:
            raise KeyError(
                f"torch key {key!r} -> no template leaf among {candidates}")

        if full[-1] == "kernel" and arr.ndim == 4:
            arr = arr.transpose(2, 3, 1, 0)  # OIHW -> HWIO
        elif full[-1] == "kernel" and arr.ndim == 2:
            # nn.Linear (out, in) -> a Dense kernel, or a 1x1 convolution's
            arr = arr.T if flat_tmpl[full].ndim == 2 else arr.T[None, None]
        want = flat_tmpl[full].shape
        if tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"shape mismatch for {key}: torch {arr.shape} vs "
                f"flax {want} at {'/'.join(full)}")
        if full in out:
            raise ValueError(f"duplicate write to {'/'.join(full)}")
        out[full] = arr.astype(np.asarray(flat_tmpl[full]).dtype)

    if "transformer" in template["params"]:
        for full in set(flat_tmpl) - set(out):
            if _GMFLOW_INERT_BIAS.match("/".join(full)):
                out[full] = np.zeros_like(np.asarray(flat_tmpl[full]))
    missing = sorted(set(flat_tmpl) - set(out))
    if missing:
        raise ValueError(
            "unfilled template leaves: "
            + ", ".join("/".join(m) for m in missing[:10]))

    tree = _unflatten(out)
    result = {"params": tree["params"]}
    if "batch_stats" in tree:
        result["batch_stats"] = tree["batch_stats"]
    elif "batch_stats" in template:
        result["batch_stats"] = template["batch_stats"]
    return result


def make_template(model_cfg: RAFTConfig):
    """Init-shape variables tree for the converter to fill: the names,
    shapes and dtypes of the model's ``init`` (``eval_shape``: nothing is
    computed), as zeros."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.raft import RAFT

    model = RAFT(model_cfg)
    img = jnp.zeros((1, 48, 64, 3))
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k, "dropout": k}, img, img, iters=1),
        jax.random.PRNGKey(0))
    variables = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), dict(shapes))
    return {"params": variables["params"],
            "batch_stats": dict(variables.get("batch_stats", {}))}


def convert_checkpoint(pth_path: str, arch: str = "full"):
    """Load a reference ``.pth`` and return converted flax variables."""
    import torch

    sd = torch.load(pth_path, map_location="cpu")
    return convert_state_dict(sd, make_template(RAFTConfig.preset(arch)))


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="Convert a reference RAFT, GMA, SEA-RAFT or GMFlow "
                    ".pth to an orbax checkpoint")
    p.add_argument("pth", help="path to torch checkpoint")
    p.add_argument("out", help="output orbax checkpoint directory")
    add_arch_argument(p)
    args = p.parse_args(argv)

    from raft_tpu.train.checkpoint import save_variables

    variables = convert_checkpoint(args.pth, arch=arch_from_args(args))
    save_variables(args.out, variables)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
