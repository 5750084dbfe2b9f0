"""Token traffic for language-model cells: a traffic mix stays a data file
(``chipbench/traffic/<name>.json``); this module turns its parameters into
token ids from the run's seed."""


def token_pool(seed, batches, sequences, seq_len, vocab):
    """*batches* seeded batches of *sequences* documents of *seq_len* + 1
    uniform token ids from the vocabulary held here, cut into inputs and
    next-token labels (the ids shifted by one): two float32 arrays
    [batches, sequences, seq_len], the Module's input and label dtype,
    made on the default device in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        ids = jax.random.randint(key, (batches, sequences, seq_len + 1), 0,
                                 vocab).astype(jnp.float32)
        return ids[..., :-1], ids[..., 1:]

    return make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
