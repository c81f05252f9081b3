"""Lucas-Kanade vs RAFT comparison (reference ``a_lk_vs_raft.py:1-143``).

Sparse LK tracks (FAST keypoints + ``cv2.calcOpticalFlowPyrLK``) drawn over
the dense RAFT flow visualization, plus an agreement statistic: median
endpoint difference between the LK tracks and the dense flow sampled at the
same keypoints.  Headless: writes a side-by-side PNG instead of the
reference's matplotlib window (a_lk_vs_raft.py:96-127).
"""

from __future__ import annotations

import argparse
import os.path as osp

from raft_tpu.cli import (add_arch_argument, arch_from_args,
                          parse_with_arch)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="LK vs RAFT comparison")
    p.add_argument("--model", required=True, help="checkpoint directory")
    p.add_argument("--image1", required=True)
    p.add_argument("--image2", required=True)
    p.add_argument("--out", default="lk_vs_raft.png")
    add_arch_argument(p)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--max_corners", type=int, default=200)
    return parse_with_arch(p, argv)


def lk_tracks(img1_rgb, img2_rgb, max_corners=200):
    """FAST keypoints on frame1 tracked into frame2 with pyramidal LK
    (reference a_lk_vs_raft.py:97-115).  Returns (p0, p1) float32 arrays
    of matched (x, y) points."""
    import cv2
    import numpy as np

    g1 = cv2.cvtColor(img1_rgb, cv2.COLOR_RGB2GRAY)
    g2 = cv2.cvtColor(img2_rgb, cv2.COLOR_RGB2GRAY)
    fast = cv2.FastFeatureDetector_create(threshold=25)
    kps = fast.detect(g1, None)
    kps = sorted(kps, key=lambda k: -k.response)[:max_corners]
    if not kps:
        return (np.zeros((0, 2), np.float32),) * 2
    p0 = np.float32([k.pt for k in kps]).reshape(-1, 1, 2)
    p1, st, _ = cv2.calcOpticalFlowPyrLK(
        g1, g2, p0, None, winSize=(21, 21), maxLevel=3)
    ok = st.reshape(-1) == 1
    return p0.reshape(-1, 2)[ok], p1.reshape(-1, 2)[ok]


def main(argv=None):
    args = parse_args(argv)

    import cv2
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.cli.evaluate import load_model_variables
    from raft_tpu.config import RAFTConfig
    from raft_tpu.data.frame_utils import read_image
    from raft_tpu.evaluate import make_eval_fn
    from raft_tpu.ops.pad import InputPadder
    from raft_tpu.utils.flow_viz import flow_to_image

    model_cfg = RAFTConfig.preset(
        arch_from_args(args), compute_dtype="bfloat16")
    variables = load_model_variables(args.model, model_cfg.arch)
    if "batch_stats" not in variables:
        variables = dict(variables, batch_stats={})
    eval_fn = make_eval_fn(model_cfg, args.iters)

    img1 = read_image(args.image1)
    img2 = read_image(args.image2)
    j1 = jnp.asarray(img1, jnp.float32)[None]
    j2 = jnp.asarray(img2, jnp.float32)[None]
    padder = InputPadder(j1.shape, multiple=model_cfg.pad_multiple)
    p1_, p2_ = padder.pad(j1, j2)
    _, flow_up = eval_fn(variables, p1_, p2_)
    flow = np.asarray(padder.unpad(flow_up)[0])

    p0, p1 = lk_tracks(img1, img2, args.max_corners)
    viz = flow_to_image(flow).copy()
    overlay = img1.copy()
    for (x0, y0), (x1, y1) in zip(p0, p1):
        a, b = (int(round(x0)), int(round(y0))), (int(round(x1)),
                                                  int(round(y1)))
        cv2.arrowedLine(overlay, a, b, (0, 255, 0), 1, tipLength=0.3)
        cv2.arrowedLine(viz, a, b, (0, 0, 0), 1, tipLength=0.3)

    if len(p0):
        xi = np.clip(p0[:, 0].round().astype(int), 0, flow.shape[1] - 1)
        yi = np.clip(p0[:, 1].round().astype(int), 0, flow.shape[0] - 1)
        raft_at_kp = flow[yi, xi]
        diff = np.linalg.norm((p1 - p0) - raft_at_kp, axis=1)
        print(f"{len(p0)} LK tracks; median |LK - RAFT| = "
              f"{np.median(diff):.2f}px", flush=True)

    side = np.concatenate([overlay, viz], axis=1)
    cv2.imwrite(args.out, cv2.cvtColor(side, cv2.COLOR_RGB2BGR))
    print(f"wrote {osp.abspath(args.out)}", flush=True)


if __name__ == "__main__":
    main()
