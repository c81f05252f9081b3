"""sha of the train cell's traced step (make_train_step at the cell's size: batch 16, 368x496, bf16, 12
iterations, the CLI's defaults as benchmark/kinds/train.py builds them), with the backend answering "tpu".
Run from the root of a checkout: PYTHONPATH=. JAX_PLATFORMS=cpu python scripts/train_step_sha.py"""
import hashlib, json, re, sys
import jax, jax.numpy as jnp
from raft_tpu.cli import train as cli
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.parallel.mesh import make_mesh
from raft_tpu.train.optim import make_optimizer
from raft_tpu.train.step import init_state, make_train_step

tr = json.load(open("benchmark/traffic/chairs_b16.json"))
crop, batch = tuple(tr["crop"]), int(tr["batch_per_chip"])
jax.default_backend = lambda: "tpu"
args = cli.parse_args(["--stage", "chairs", "--image_size", str(crop[0]), str(crop[1]), "--batch_size", str(batch),
                       "--iters", str(tr.get("iters", 12))] + [str(a) for a in tr.get("flags", [])])
corr_impl = cli.default_corr_impl() if args.corr_impl == "auto" else args.corr_impl
model_cfg = RAFTConfig.full(dropout=args.dropout, corr_impl=corr_impl,
    compute_dtype="bfloat16" if args.precision == "bf16" else "float32", corr_dtype=args.corr_dtype,
    corr_precision=args.corr_precision, remat=args.remat != "none",
    remat_policy=args.remat if args.remat != "none" else "save_corr", remat_upsample=bool(args.remat_upsample))
tcfg = TrainConfig(name="bench", stage="chairs", lr=args.lr, num_steps=args.num_steps, batch_size=batch,
    image_size=crop, iters=args.iters, wdecay=args.wdecay, epsilon=args.epsilon, clip=args.clip, gamma=args.gamma,
    add_noise=args.add_noise, accum_steps=args.accum_steps, nonfinite_guard=bool(args.nonfinite_guard))
model = RAFT(model_cfg)
tx = make_optimizer(tcfg.lr, tcfg.num_steps, tcfg.wdecay, tcfg.epsilon, tcfg.clip)
key = jax.random.PRNGKey(0)
state = jax.eval_shape(lambda: init_state(model, tx, key, crop))
S = jax.ShapeDtypeStruct
H, W = crop
b = {"image1": S((batch, H, W, 3), jnp.float32), "image2": S((batch, H, W, 3), jnp.float32),
     "flow": S((batch, H, W, 2), jnp.float32), "valid": S((batch, H, W), jnp.float32)}
mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
step = make_train_step(model, tx, tcfg, mesh, donate=False)
text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(step)(state, b, key)))
print(f"corr_impl given to the model: {corr_impl}; pallas_calls {text.count('pallas_call[')}; lines {len(text.splitlines())}; "
      f"sha256[:16] {hashlib.sha256(text.encode()).hexdigest()[:16]}")
