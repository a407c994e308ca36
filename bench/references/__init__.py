"""Plain references, one module per transform kind: numpy in float64.

Each module has ``transform(x) -> ndarray`` over the last axis.  They
import nothing of the program under test.
"""
