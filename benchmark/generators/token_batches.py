"""Training input: a new [batch, seq] array of token ids for every step,
made on the host from the seed (the trainer moves it to the device
inside its step, so the input pipeline is part of what is timed)."""
import numpy as np


class TokenBatches:
    def __init__(self, traffic, seed):
        self.shape = (int(traffic['batch']), int(traffic['seq_len']))
        self.id_limit = int(traffic['id_limit'])
        self.seed = int(seed)

    def batch(self, step):
        rng = np.random.default_rng([self.seed, int(step)])
        return rng.integers(0, self.id_limit, size=self.shape,
                            dtype=np.int32)


def make(traffic, seed):
    return TokenBatches(traffic, seed)
