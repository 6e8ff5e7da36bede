"""The copied Gaussian shard generator starts identical to the program's
own fleet build, and every bit of a large seed counts."""
import jax
import numpy as np

from chipbench import gen
from chipbench.common import seed_key


def test_shards_match_the_programs_fleet_build():
    from repro.data import make_gaussian_dataset, partition_iid
    seed, clients, per = 7, 40, 16
    x, y = gen.gaussian_fleet(seed_key(seed), n_clients=clients,
                              per_client=per, features=5)
    key = jax.random.PRNGKey(seed)
    shards = partition_iid(key, make_gaussian_dataset(key, clients * per),
                           clients)
    ours = gen.host_shards(x, y)
    assert len(ours) == len(shards) == clients
    for a, b in zip(ours, shards):
        np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
        np.testing.assert_array_equal(np.asarray(a["y"]), np.asarray(b["y"]))


def test_large_seeds_keep_their_high_bits():
    a = np.asarray(seed_key(5))
    b = np.asarray(seed_key(2 ** 33 + 5))
    c = np.asarray(seed_key(2 ** 31 + 5))
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, c)
    np.testing.assert_array_equal(a, np.asarray(jax.random.PRNGKey(5)))
