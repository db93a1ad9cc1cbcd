"""Between a configuration's ``layout`` and a gluon network's parameters:
the two walk in the same order (``collect_params()`` lists parameters in
the order the blocks were built), so a tuple of arrays in layout order is
all either side needs of the other."""
import numpy as np


def set_all(net, arrays):
    """Set every parameter of ``net`` to the array at its place. On a
    fresh network ``set_data`` also resolves the deferred shapes, so no
    eager forward pass is needed to materialize them."""
    from mxnet_tpu.ndarray import NDArray

    params = list(net.collect_params().values())
    if len(params) != len(arrays):
        raise AssertionError(
            f"the layout lists {len(arrays)} parameters, the gluon "
            f"network has {len(params)}")
    for p, a in zip(params, arrays):
        p.set_data(NDArray(a))


def export(net, names):
    """``{name: float32 numpy array}`` of the network as it is."""
    import jax

    params = list(net.collect_params().values())
    return {name: np.asarray(jax.device_get(p.data()._data))
            .astype(np.float32) for name, p in zip(names, params)}
