// The benchmark's generator of gossip DAGs in which Byzantine validators
// equivocate, kept with the benchmark so that the fork cells' input
// cannot change under a later PR.
//
// The sync model is gossip_dag.cpp's (the upstream live loop, mpitid/babble
// node/node.go:193-222): each step one receiver syncs from one random
// sender and mints an event with parents (own head, sender head); the
// receivers are a seeded shuffle of a fixed multiset, so every validator
// mints the same number of events and every seed gives the same sizes.
//
// The forkers (n_forkers of them, drawn from the seed) each equivocate
// once, at the midpoint of their chain: once a forker has minted its
// (per / 2)-th event (per = events per validator), that event is
// gossiped (the next sync by another validator takes the forker as its
// sender), and the forker's next event is minted on its event two back,
// so that two of its events share one index.  It continues on the new
// branch; the others keep taking its newest event as their other-parent.
// The gossiped event makes the fork visible to the honest validators.
// Should the shuffle hand the forker its next turn before another
// validator has synced, that turn is swapped with the next turn of
// another validator (counts are unchanged).  Deterministic in the seed
// (splitmix64).
//
// Build: g++ -O3 -shared -fPIC (benchmark/reference/fork_native.py).

#include <cstdint>
#include <cstring>
#include <algorithm>
#include <vector>

extern "C" {

static inline uint64_t splitmix64(uint64_t *state) {
    uint64_t z = (*state += 0x9E3779B97F4A7C15ULL);
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
}

// Fills the struct-of-arrays DAG (slot order is topological).  Arrays
// are caller-allocated with n_events entries; forkers gets n_forkers.
// Returns the number of distinct levels, or -1 on bad arguments.
long fork_dag(
    uint64_t seed, int32_t n, int64_t n_events, int32_t n_forkers,
    int64_t ts_granularity_ns, int64_t base_ts,
    int32_t *sp, int32_t *op, int32_t *creator, int32_t *seq,
    int64_t *ts, uint8_t *mbit, int32_t *levels, int32_t *forkers
) {
    if (n < 2 || n_events < n || n_forkers < 0 || n_forkers >= n)
        return -1;
    uint64_t st = seed * 2ULL + 1ULL;
    std::vector<int32_t> heads(n), seqs(n, 1), minted(n, 1);
    int64_t k = 0;
    int32_t max_level = 0;
    for (int32_t i = 0; i < n; ++i, ++k) {
        sp[k] = -1; op[k] = -1; creator[k] = i; seq[k] = 0;
        ts[k] = base_ts; levels[k] = 0;
        mbit[k] = (uint8_t)(splitmix64(&st) & 1ULL);
        heads[i] = (int32_t)k;
    }
    int64_t m = n_events - k;
    std::vector<int32_t> order(m > 0 ? m : 1), first(n);
    for (int32_t i = 0; i < n; ++i) first[i] = i;
    for (int32_t i = n - 1; i > 0; --i)
        std::swap(first[i], first[splitmix64(&st) % (uint64_t)(i + 1)]);
    for (int64_t i = 0; i < m; ++i) order[i] = first[i % n];
    for (int64_t i = m - 1; i > 0; --i)
        std::swap(order[i], order[splitmix64(&st) % (uint64_t)(i + 1)]);

    // the forkers: a seeded partial shuffle of the validators
    std::vector<int32_t> ids(n);
    for (int32_t i = 0; i < n; ++i) ids[i] = i;
    for (int32_t i = 0; i < n_forkers; ++i)
        std::swap(ids[i], ids[i + splitmix64(&st) % (uint64_t)(n - i)]);
    // per validator: 0 honest, 1 forker before its fork, 2 its orphan
    // minted and not yet synced, 3 orphan synced (fork on its next turn),
    // 4 forked
    std::vector<int32_t> state(n, 0);
    for (int32_t i = 0; i < n_forkers; ++i) {
        forkers[i] = ids[i];
        state[ids[i]] = 1;
    }
    int64_t per = n_events / n;
    int32_t fork_at = (int32_t)std::max<int64_t>(per / 2, 2);

    for (int64_t t = 1; k < n_events; ++t, ++k) {
        int32_t r = order[t - 1];
        if (state[r] == 2) {
            // the forker's orphan is not synced yet: swap its turn with
            // the next turn of another validator
            for (int64_t j = t; j < m; ++j) {
                if (order[j] != r) {
                    std::swap(order[t - 1], order[j]);
                    break;
                }
            }
            r = order[t - 1];
        }
        int32_t s = (int32_t)(splitmix64(&st) % (uint64_t)(n - 1));
        if (s >= r) s += 1;
        for (int32_t f = 0; f < n; ++f) {
            if (state[f] == 2 && f != r) {   // the orphan is gossiped
                s = f;
                state[f] = 3;
                break;
            }
        }
        int64_t raw = t * 1987963LL;
        ts[k] = base_ts + (raw / ts_granularity_ns) * ts_granularity_ns;
        int32_t sps = heads[r], opsl = heads[s];
        int32_t idx = seqs[r];
        if (state[r] == 3) {                 // equivocate: two back
            sps = sp[heads[r]];
            idx = seq[heads[r]];
            state[r] = 4;
        }
        sp[k] = sps; op[k] = opsl;
        creator[k] = r; seq[k] = idx;
        seqs[r] = idx + 1;
        int32_t lvl = 1 + std::max(levels[sps], levels[opsl]);
        levels[k] = lvl;
        if (lvl > max_level) max_level = lvl;
        mbit[k] = (uint8_t)(splitmix64(&st) & 1ULL);
        heads[r] = (int32_t)k;
        if (++minted[r] == fork_at && state[r] == 1) state[r] = 2;
    }
    return (long)(max_level + 1);
}

}  // extern "C"
