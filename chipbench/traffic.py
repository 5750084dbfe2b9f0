"""The one general traffic generator.  A traffic mix is a data file,
``chipbench/traffic/<name>.json``, of parameters that this module turns
into inputs from the run's seed; a new mix is a new file, never new code.

Every seed gets the same set of sizes in another order, so that the seed
moves the order of the work and not its amount.
"""
import numpy as np


def image_pool(seed, batches, batch, image, classes):
    """*batches* seeded batches of float32 images in [0, 1) and integer
    labels (as float32, the Module's label dtype), made on the default
    device in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (batches, batch, 3, image, image),
                               jnp.float32)
        y = jax.random.randint(ky, (batches, batch), 0, classes)
        return x, y.astype(jnp.float32)

    return make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def rows_schedule(seed, client, mix, block):
    """A client's endless schedule of rows per request: a block of *block*
    requests holding each row count in the mix's proportion, shuffled by
    (seed, client), then repeated."""
    sizes = []
    for rows, share in sorted(mix.items(), key=lambda kv: int(kv[0])):
        sizes += [int(rows)] * int(round(float(share) * block))
    if len(sizes) != block:
        raise ValueError("mix %r does not divide a block of %d" % (mix, block))
    rng = np.random.RandomState((seed * 1000003 + client) % (2 ** 32))
    return [sizes[i] for i in rng.permutation(block)]
