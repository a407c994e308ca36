"""Plain reference of the real forward transform (half spectrum), in float64."""

import numpy as np


def transform(x: np.ndarray) -> np.ndarray:
    return np.fft.rfft(np.asarray(x, np.float64), axis=-1)
