"""One tier step of the smoke hybrid sharded over 4 forced host CPU
devices against the same step on one device. Run as a script (the
device count is fixed when JAX starts): ``tests/test_sharding.py`` starts
it in a process of its own and reads its exit code."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core import TrainState, make_hetero_train_step  # noqa: E402
from repro.core.compression import default_tier_plans  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import sharded_train_step  # noqa: E402
from repro.models import get_model  # noqa: E402

# float32 round-off: the sharded matmuls and reductions sum in another
# order; a bfloat16 step would differ by about 4e-3
RTOL = 1e-4


def main() -> int:
    assert len(jax.devices()) == 4, jax.devices()
    cfg = get_smoke_config("granite-4.0-h-micro").replace(
        num_layers=4, layer_types=("mamba", "mamba", "attention", "mamba"))
    model = get_model(cfg)
    opt = optim.adamw(3e-4)
    plans = default_tier_plans(4)
    state = TrainState.create(model, opt, jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (4, 2, 33),
                                          0, cfg.vocab_size)}
    one, m1 = jax.jit(make_hetero_train_step(model, opt, plans))(state, batch)
    mesh = make_host_mesh(4)
    assert dict(mesh.shape) == {"data": 1, "model": 4}
    step, state_sh, batch_sh = sharded_train_step(model, opt, plans, mesh,
                                                  state)
    four, m4 = step(jax.device_put(state, state_sh),
                    jax.device_put(batch, batch_sh))
    sharded = [x for x in jax.tree.leaves(four["params"])
               if len(x.sharding.device_set) == 4
               and not x.sharding.is_fully_replicated]
    assert len(sharded) >= len(jax.tree.leaves(four["params"])) // 2
    np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]),
                               rtol=RTOL)
    # AdamW's first moment, (1 - b1) times the aggregated gradient
    for a, b in zip(jax.tree.leaves(four["opt"]["m"]),
                    jax.tree.leaves(one["opt"]["m"])):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=RTOL,
                                   atol=RTOL * float(np.max(np.abs(b))))
    print("sharded step matches", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
