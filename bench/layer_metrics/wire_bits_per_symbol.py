"""Bits a client downloads per decoded symbol: 8 x the bytes of the
container ``CapabilityRegistry.container_for_threads`` builds for an
(object, capability) pair (its stream plus the split metadata thinned to
that capability) over the object's symbols, averaged over the pairs the
window's requests named."""


def read(run):
    pairs = sorted({(r.name, r.cap) for r in run.requests})
    if not pairs:
        return None
    bits = [8 * run.wire_bytes(n, c) / run.sizes[n] for n, c in pairs]
    return sum(bits) / len(bits)
