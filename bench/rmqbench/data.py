"""Inputs made from ``--seed``: arrays on the device, query and request streams.

Everything here is a function of the seed alone, so the same seed gives
the same inputs on every run.  Seeds may exceed 32 bits: they are mixed
through ``numpy.random.SeedSequence`` before reaching either generator.

The paper's §5.1 generators (``make_queries``) are copied from the
program's ``repro.tune.measure`` so that no program change can move the
yardstick.  The YCSB key chooser follows the YCSB core workload's
``ScrambledZipfianGenerator`` (Cooper et al., SoCC 2010).
"""

from __future__ import annotations

import numpy as np

# YCSB ScrambledZipfianGenerator: a Zipfian over a fixed 10^10 items whose
# draws are FNV-hashed onto the live key space.
_YCSB_ITEM_COUNT = 10_000_000_000
_YCSB_ZETAN = 26.46902820178302          # zeta(10^10, 0.99), as in YCSB
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def seed_words(seed: int, stream: int = 0) -> np.ndarray:
    """Two uint32 words drawn from ``(seed, stream)``; any size of seed."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of one seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream)]))


def device_uniform(seed: int, n: int, block: int = 1 << 20):
    """``n`` i.i.d. uniform [0, 1) float32 values, made on the device.

    One jitted call writes the array block by block into its output, so
    the device holds the array plus one block of temporaries, never a
    second copy.  Block ``i`` depends only on ``(seed, i)``.
    """
    import jax
    import jax.numpy as jnp

    block = min(block, n)
    full, tail = divmod(n, block)
    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed, 0)))

    def gen(key):
        def body(i, buf):
            v = jax.random.uniform(jax.random.fold_in(key, i), (block,),
                                   jnp.float32)
            return jax.lax.dynamic_update_slice(buf, v, (i * block,))

        buf = jax.lax.fori_loop(0, full, body,
                                jnp.zeros((n,), jnp.float32))
        if tail:
            v = jax.random.uniform(jax.random.fold_in(key, full), (block,),
                                   jnp.float32)[:tail]
            buf = jax.lax.dynamic_update_slice(buf, v, (full * block,))
        return buf

    return jax.jit(gen)(key)


def host_copy(x, block: int = 1 << 24) -> np.ndarray:
    """A device array copied to the host in blocks (bounded temporaries)."""
    import jax

    n = x.shape[0]
    out = np.empty(n, np.float32)
    for s in range(0, n, block):
        out[s:s + block] = np.asarray(jax.device_get(x[s:s + block]))
    return out


# ---------------------------------------------------------------------------
# paper §5.1 range classes (copy of repro.tune.measure.make_queries)
# ---------------------------------------------------------------------------
def make_queries(n: int, m: int, kind: str, gen: np.random.Generator):
    """Paper §5.1 range-size classes (large / medium / small / mixed).

    large: uniform in [1, n]; medium: log-normal around n^0.6, sigma 0.3;
    small: log-normal around n^0.3, sigma 0.3; mixed: equal thirds,
    shuffled.  Left borders are uniform in [0, n - s].
    """

    def sizes(kind, count):
        if kind == "large":
            return gen.integers(1, n + 1, count)
        if kind == "medium":
            s = gen.lognormal(np.log(n ** 0.6), 0.3, count)
            return np.clip(s.astype(np.int64), 1, n)
        if kind == "small":
            s = gen.lognormal(np.log(n ** 0.3), 0.3, count)
            return np.clip(s.astype(np.int64), 1, n)
        if kind == "mixed":
            parts = [sizes(k, count // 3 + 1)
                     for k in ("large", "medium", "small")]
            s = np.concatenate(parts)[:count]
            gen.shuffle(s)
            return s
        raise ValueError(f"unknown range class {kind!r}")

    s = sizes(kind, m)
    ls = (gen.random(m) * (n - s + 1)).astype(np.int64)
    rs = ls + s - 1
    return ls.astype(np.int32), rs.astype(np.int32)


# ---------------------------------------------------------------------------
# YCSB core workload key chooser and request stream
# ---------------------------------------------------------------------------
def _zipfian(u: np.ndarray, theta: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` for uniforms ``u`` (Gray et al.)."""
    items = _YCSB_ITEM_COUNT
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / _YCSB_ZETAN)
    uz = u * _YCSB_ZETAN
    v = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    v = np.where(uz < 1.0 + 0.5 ** theta, 1, v)
    return np.where(uz < 1.0, 0, v)


def fnv_hash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 bytes of ``v``, abs'd."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * _FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64)).astype(np.int64)


def scrambled_zipfian(count: int, records: int, theta: float,
                      gen: np.random.Generator) -> np.ndarray:
    """``count`` keys in ``[0, records)``, YCSB scrambled-Zipfian."""
    if theta != 0.99:
        raise ValueError("the YCSB constant zeta(10^10) is for theta 0.99")
    return fnv_hash64(_zipfian(gen.random(count), theta)) % records


def request_stream(traffic: dict, records: int, seed: int) -> dict:
    """The open-loop request stream of one run, drawn from the seed.

    Arrivals are Poisson at ``traffic["rate_per_s"]`` over
    ``seconds``; each request is an insert with probability
    ``insert_share`` and otherwise a scan whose start key is
    scrambled-Zipfian over the initial records and whose length is
    uniform in ``[1, max_scan_length]``.
    """
    gen = rng(seed, 1)
    rate = float(traffic["rate_per_s"])
    count = int(np.ceil(rate * traffic["seconds"] * 1.2)) + 64
    at = np.cumsum(gen.exponential(1.0 / rate, count))
    count = int(np.searchsorted(at, traffic["seconds"]))
    at = at[:count]
    insert = gen.random(count) < float(traffic["insert_share"])
    span = int(traffic["max_scan_length"])
    keys = scrambled_zipfian(count, records - span + 1,
                             float(traffic["zipf_theta"]), gen)
    lens = gen.integers(1, span + 1, count)
    ls = keys.astype(np.int32)
    rs = (keys + lens - 1).astype(np.int32)
    values = gen.random(count, dtype=np.float32)
    return {"at": at, "insert": insert, "ls": ls, "rs": rs,
            "values": values}
