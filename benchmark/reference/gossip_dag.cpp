// The benchmark's DAG generator, kept with the benchmark so that the
// replay cells' input cannot change under a later PR.
//
// Gossip shape of the upstream live loop (mpitid/babble node/node.go:
// 193-222): each step one receiver syncs from one random sender and mints
// an event with parents (own head, sender head).  The receivers are a
// seeded shuffle of a fixed multiset: every validator mints the same
// number of events (one more for the first (n_events - n) % n of a
// shuffled order), so every seed gives the same sizes, the longest chain
// included, in another order.  Deterministic in the seed (splitmix64).
//
// Build: g++ -O3 -shared -fPIC (benchmark/reference/native.py).

#include <cstdint>
#include <cstring>
#include <algorithm>

extern "C" {

static inline uint64_t splitmix64(uint64_t *state) {
    uint64_t z = (*state += 0x9E3779B97F4A7C15ULL);
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
}

// Fills the struct-of-arrays DAG.  Arrays are caller-allocated with
// n_events entries.  Returns the number of distinct levels.
long gossip_dag(
    uint64_t seed, int32_t n, int64_t n_events,
    int64_t ts_granularity_ns, int64_t base_ts,
    int32_t *sp, int32_t *op, int32_t *creator, int32_t *seq,
    int64_t *ts, uint8_t *mbit, int32_t *levels, int32_t *heads /* [n] */
) {
    uint64_t st = seed * 2ULL + 1ULL;
    int64_t k = 0;
    int32_t max_level = 0;
    for (int32_t i = 0; i < n && k < n_events; ++i, ++k) {
        sp[k] = -1; op[k] = -1; creator[k] = i; seq[k] = 0;
        ts[k] = base_ts; levels[k] = 0;
        mbit[k] = (uint8_t)(splitmix64(&st) & 1ULL);
        heads[i] = (int32_t)k;
    }
    int64_t m = n_events - k;
    int32_t *order = new int32_t[m > 0 ? m : 1];
    int32_t *first = new int32_t[n];
    for (int32_t i = 0; i < n; ++i) first[i] = i;
    for (int32_t i = n - 1; i > 0; --i)
        std::swap(first[i], first[splitmix64(&st) % (uint64_t)(i + 1)]);
    for (int64_t i = 0; i < m; ++i) order[i] = first[i % n];
    for (int64_t i = m - 1; i > 0; --i)
        std::swap(order[i], order[splitmix64(&st) % (uint64_t)(i + 1)]);
    int32_t *seqs = new int32_t[n];
    for (int32_t i = 0; i < n; ++i) seqs[i] = 1;

    for (int64_t t = 1; k < n_events; ++t, ++k) {
        int32_t r = order[t - 1];
        int32_t s = (int32_t)(splitmix64(&st) % (uint64_t)(n - 1));
        if (s >= r) s += 1;
        int64_t raw = t * 1987963LL;
        ts[k] = base_ts + (raw / ts_granularity_ns) * ts_granularity_ns;
        int32_t sps = heads[r], opsl = heads[s];
        sp[k] = sps; op[k] = opsl;
        creator[k] = r; seq[k] = seqs[r]++;
        int32_t lvl = 1 + std::max(levels[sps], levels[opsl]);
        levels[k] = lvl;
        if (lvl > max_level) max_level = lvl;
        mbit[k] = (uint8_t)(splitmix64(&st) & 1ULL);
        heads[r] = (int32_t)k;
    }
    delete[] seqs;
    delete[] first;
    delete[] order;
    return (long)(max_level + 1);
}

}  // extern "C"
