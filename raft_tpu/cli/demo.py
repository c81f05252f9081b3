"""Inference demo (reference ``demo.py``): run RAFT on consecutive frame
pairs from a directory and write flow visualizations.

Headless redesign: the reference pops a cv2.imshow window (demo.py:26-39);
here each pair writes ``<out>/<name>_flow.png`` — the input frame stacked
over the Baker color-wheel flow image — which works on a TPU VM with no
display.
"""

from __future__ import annotations

import argparse
import glob
import os
import os.path as osp

from raft_tpu.cli import (add_arch_argument, arch_from_args,
                          parse_with_arch)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="RAFT-TPU demo")
    p.add_argument("--model", required=True, help="checkpoint directory")
    p.add_argument("--path", default=None,
                   help="directory of frames (sorted, consecutive pairs); "
                        "defaults to data_abel/ when present (the "
                        "reference fork's signature sample, demo.py:69), "
                        "else demo-frames/")
    p.add_argument("--out", default="demo-out", help="output directory")
    add_arch_argument(p)
    p.add_argument("--precision", default="bf16", choices=["bf16", "fp32"])
    p.add_argument("--alternate_corr", action="store_true")
    p.add_argument("--iters", type=int, default=20)  # demo.py:62
    return parse_with_arch(p, argv)


def main(argv=None):
    args = parse_args(argv)
    if args.path is None:
        if osp.isdir("data_abel"):       # the fork's sample (demo.py:69)
            args.path = "data_abel"
        elif osp.isdir("demo-frames"):
            args.path = "demo-frames"
        else:
            # bare clone, cwd elsewhere: the repo bundles a procedural
            # sample (regenerable via scripts/make_demo_frames.py) next
            # to the package.
            args.path = osp.join(osp.dirname(osp.dirname(
                osp.dirname(osp.abspath(__file__)))), "demo-frames")
            if not osp.isdir(args.path):
                raise SystemExit(
                    f"no frame directory: pass --path, or generate the "
                    f"bundled sample with scripts/make_demo_frames.py "
                    f"(looked for ./data_abel, ./demo-frames, "
                    f"{args.path})")

    import jax.numpy as jnp
    import numpy as np
    from PIL import Image

    from raft_tpu.cli.evaluate import load_model_variables
    from raft_tpu.config import RAFTConfig
    from raft_tpu.data.frame_utils import read_image
    from raft_tpu.evaluate import make_eval_fn
    from raft_tpu.ops.pad import InputPadder
    from raft_tpu.utils.flow_viz import flow_to_image

    from raft_tpu.evaluate import default_alternate_corr_impl

    compute_dtype = "bfloat16" if args.precision == "bf16" else "float32"
    model_cfg = RAFTConfig.preset(
        arch_from_args(args), compute_dtype=compute_dtype,
        corr_impl=default_alternate_corr_impl()
        if args.alternate_corr else "allpairs")
    variables = load_model_variables(args.model, model_cfg.arch)
    if "batch_stats" not in variables:
        variables = dict(variables, batch_stats={})
    eval_fn = make_eval_fn(model_cfg, args.iters)

    frames = sorted(
        glob.glob(osp.join(args.path, "*.png"))
        + glob.glob(osp.join(args.path, "*.jpg")))
    assert len(frames) >= 2, f"need >=2 frames in {args.path}"
    os.makedirs(args.out, exist_ok=True)

    for file1, file2 in zip(frames[:-1], frames[1:]):
        img1 = jnp.asarray(read_image(file1), jnp.float32)[None]
        img2 = jnp.asarray(read_image(file2), jnp.float32)[None]
        padder = InputPadder(img1.shape, multiple=model_cfg.pad_multiple)
        img1p, img2p = padder.pad(img1, img2)
        _, flow_up = eval_fn(variables, img1p, img2p)
        flow = np.asarray(padder.unpad(flow_up)[0])

        viz = flow_to_image(flow)
        stacked = np.concatenate(
            [np.asarray(img1[0], np.uint8), viz], axis=0)
        name = osp.splitext(osp.basename(file1))[0]
        out_path = osp.join(args.out, f"{name}_flow.png")
        Image.fromarray(stacked).save(out_path)
        print(f"{file1} -> {out_path}  "
              f"|flow| max {np.abs(flow).max():.1f}px", flush=True)


if __name__ == "__main__":
    main()
