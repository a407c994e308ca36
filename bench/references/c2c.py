"""Plain reference of the forward complex transform, in float64."""

import numpy as np


def transform(x: np.ndarray) -> np.ndarray:
    return np.fft.fft(np.asarray(x, np.complex128), axis=-1)
