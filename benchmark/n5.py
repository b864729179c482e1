"""N5 stores through tensorstore's own driver, independent of the program's
storage layer.  N5 lists dimensions fastest-first, so the C-order (z, y, x)
array is the transpose of what tensorstore returns."""

import numpy as np


# ---------------------------------------------------------------------------

def open_(path: str, key: str):
    import tensorstore as ts

    return ts.open({"driver": "n5",
                    "kvstore": {"driver": "file", "path": path},
                    "path": key}).result()


def read(path: str, key: str, begin=None, end=None) -> np.ndarray:
    arr = open_(path, key)
    shape = tuple(arr.shape[::-1])
    begin = begin or (0,) * len(shape)
    end = end or shape
    sl = tuple(slice(b, e) for b, e in zip(begin[::-1], end[::-1]))
    return np.ascontiguousarray(arr[sl].read().result().transpose())


def write(path: str, key: str, data: np.ndarray, chunks) -> None:
    """Create (or replace) the dataset ``key`` holding ``data``, of any
    rank, in chunks of ``chunks`` (one entry per axis, C order)."""
    import tensorstore as ts

    if len(chunks) != data.ndim:
        raise ValueError(f"chunks {tuple(chunks)} for a {data.ndim}-d array")
    arr = ts.open({
        "driver": "n5", "kvstore": {"driver": "file", "path": path},
        "path": key,
        "metadata": {"dimensions": list(data.shape[::-1]),
                     "blockSize": list(chunks[::-1]),
                     "dataType": str(data.dtype),
                     "compression": {"type": "raw"}},
        "create": True, "delete_existing": True}).result()
    arr.write(data.transpose()).result()


def write_region(path: str, key: str, begin, data: np.ndarray) -> None:
    """Overwrite the region of an existing dataset that starts at ``begin``
    with ``data``, cast to the dataset's type."""
    arr = open_(path, key)
    sl = tuple(slice(b, b + s) for b, s in zip(begin[::-1],
                                               data.shape[::-1]))
    arr[sl].write(data.transpose().astype(arr.dtype.numpy_dtype)).result()
