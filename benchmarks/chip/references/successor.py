"""Plain reference of a D1HT lookup: the successor of a key, the first
live id at or after it on the 2^64 ring, wrapping past the largest.
numpy on sorted uint64 ids; imports nothing of the program."""
import numpy as np


def successor(live: np.ndarray, keys: np.ndarray) -> np.ndarray:
    return live[np.searchsorted(live, keys) % live.size]
