"""Decoder-adaptive thinning on the served path (paper §3.3).

One ``rand_50`` asset, ingested once at 64 splits, is served through
``DecodeService`` and the broker on the Pallas walk to clients that decode
at 64, 8 and 2 splits.  Each client receives the container thinned to its
splits; the service counts those containers' bytes as it answers.  On the
CPU the kernel runs interpreted; the ``tiled`` case lowers the kernel's
VMEM limit so that the 2-split walk (1,536 steps) runs in T tiles, as a
16-split client's walk of the 10 MB asset does on a TPU v5e.
"""

import numpy as np
import pytest

from repro.core.rans import RansParams, StaticModel
from repro.kernels.rans_decode import rans_decode
from repro.runtime.pipeline import CapabilityRegistry, ControllerConfig
from repro.runtime.serve import DecodeService

CAPABILITIES = (64, 8, 2)


def _rand50(size: int, seed: int) -> np.ndarray:
    """The paper's ``rand_50`` (arXiv 2306.12141 §5.1 Table 4)."""
    vals = np.random.default_rng(seed).exponential(2550.0 / 50, size=size)
    return np.minimum(vals, 255).astype(np.int64)


@pytest.mark.parametrize("vmem", ["v5e", "tiled"])
def test_thinned_containers_served_through_broker(monkeypatch, vmem):
    if vmem == "tiled":
        monkeypatch.setattr(rans_decode, "VMEM_LIMIT_BYTES",
                            rans_decode.block_vmem_bytes(256, 8, 2048))
    asset = _rand50(64 * 1024, seed=50)
    model = StaticModel.from_symbols(asset, 256,
                                     RansParams(n_bits=11, ways=32))
    svc = DecodeService(model, impl="pallas")
    svc.ingest("asset", asset, 64)
    # No speculation: every walk the counters see answers a request.
    with svc.start_pipeline(config=ControllerConfig(
            max_batch=1, batch_sizes=(1,)), predictive=False) as broker:
        before = broker.snapshot()
        tickets = [svc.submit("asset", cap) for cap in CAPABILITIES]
        answers = [np.asarray(t.result(timeout=600)) for t in tickets]
        after = broker.snapshot()
    for out in answers:
        np.testing.assert_array_equal(out, asset)

    registry = CapabilityRegistry(svc)
    sizes = [len(registry.container_for_threads("asset", cap))
             for cap in CAPABILITIES]
    assert sizes[0] > sizes[1] > sizes[2]
    total = svc.metrics()["recoil_serve_wire_bytes_total"]["values"][""]
    assert total == sum(sizes)
    assert after["wire_bytes"] - before["wire_bytes"] == sum(sizes)

    plans = [svc.prepare_request("asset", cap) for cap in CAPABILITIES]
    tiles = [p.statics["n_steps"] // p.statics["tile_steps"] for p in plans]
    assert after["walk_tiles"] - before["walk_tiles"] == sum(
        p.walk_tiles for p in plans)
    assert (max(tiles) > 1) == (vmem == "tiled")
