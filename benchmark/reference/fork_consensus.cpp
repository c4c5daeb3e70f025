// The benchmark's plain reference of fork-aware hashgraph consensus: the
// twin of consensus.cpp for DAGs in which validators equivocate, kept
// with the benchmark so that no PR which claims a gain can change the
// yardstick.
//
// Semantics are those of the program's definition-first oracle
// (babble_tpu/consensus/byzantine.py, after the hashgraph paper, L. Baird,
// SWIRLDS-TR-2016-01), which coincide with the upstream algorithm on
// fork-free DAGs:
//   fork(w, z): same creator, neither a self-ancestor of the other;
//   see(x, y): y is an ancestor of x, and x's ancestry holds no fork pair
//     by y's creator;
//   strongly_see(x, y): events of >= 2n/3+1 CREATORS w with see(x, w) and
//     see(w, y);
//   round and witness (DivideRounds, counting the creators of the round's
//   witnesses), fame with a coin every n rounds (the vote tally counts one
//   strongly seen witness per creator), round received, and the median
//   of the famous witnesses' oldest self-ancestors to see the event, over
//   the clamped timestamps (ForkDag.eff_ts, core/dag.py clamp_eff_ts).
//
// The formulation is the upstream's coordinates (mpitid/babble
// hashgraph/hashgraph.go:399-494), kept per CHAIN instead of per creator: a
// chain is the self-parent path from a creator's root to one of its tips,
// so a forker has one chain per branch and its chains share their prefix.
// la[x, c]: highest position on chain c among x's ancestors; fd[x, c]:
// first position on chain c of a descendant of x (UpdateAncestorFirst-
// Descendant's walk).  Fork pairs visible to x, see and strongly-see all
// read those two tables.
//
// ts_rule 0 is the reference, ts_rule 1 a CONTROL: the mean, not the
// median, of the famous witnesses' times.  fork_blind 1 is the second
// CONTROL: see is plain ancestry (no fork pair is ever detected), which
// lets an equivocator's events count and be ordered.  The comparison
// behind `correct` has to reject both.
//
// Build: g++ -O3 -shared -fPIC (benchmark/reference/fork_native.py).

#include <cstdint>
#include <cstring>
#include <vector>
#include <algorithm>
#include <unordered_map>

namespace {

constexpr int32_t I32_MAX = INT32_MAX;
// core/dag.py TS_CLAMP_WINDOW_NS
constexpr int64_t TS_CLAMP_WINDOW_NS = 600000000000LL;

struct ForkReference {
    int32_t n;
    int64_t e;
    const int32_t *sp, *op, *creator, *seq;
    const int64_t *ts;
    const uint8_t *mbit;
    int32_t ts_rule, fork_blind, super_majority;

    int32_t C = 0;                                  // chains
    std::vector<std::vector<int32_t>> chain_ev;     // chain -> slots
    std::vector<std::vector<int32_t>> cr_chains;    // creator -> chains
    std::vector<int32_t> home;                      // slot -> a chain of it
    std::vector<std::vector<int32_t>> on;           // slot -> its chains
    std::vector<int32_t> la, fd;                    // [E, C]
    std::vector<uint8_t> det;                       // [E, N]
    std::vector<int64_t> eff;

    std::vector<std::vector<int32_t>> witnesses;    // round -> slots
    std::vector<int32_t> round;
    std::vector<uint8_t> witness;
    std::vector<int8_t> fame;  // per event: -1 not witness, 0 undec, 1 T, 2 F
    std::vector<int32_t> rr;
    std::vector<int64_t> cts;

    ForkReference(int32_t n_, int64_t e_, const int32_t *sp_,
                  const int32_t *op_, const int32_t *creator_,
                  const int32_t *seq_, const int64_t *ts_,
                  const uint8_t *mbit_, int32_t ts_rule_, int32_t fork_blind_)
        : n(n_), e(e_), sp(sp_), op(op_), creator(creator_), seq(seq_),
          ts(ts_), mbit(mbit_), ts_rule(ts_rule_), fork_blind(fork_blind_),
          super_majority(2 * n_ / 3 + 1), cr_chains(n_), home(e_, -1),
          on(e_), det((size_t)e_ * n_, 0), eff(e_), round(e_, -1),
          witness(e_, 0), fame(e_, -1), rr(e_, -1), cts(e_, 0) {}

    // a topological DAG whose non-roots have both parents, and whose
    // self-parent is of the same creator at the index below
    bool valid() const {
        for (int64_t x = 0; x < e; ++x) {
            if (creator[x] < 0 || creator[x] >= n) return false;
            if (sp[x] < 0) {
                if (op[x] >= 0 || seq[x] != 0) return false;
                continue;
            }
            if (sp[x] >= x || op[x] < 0 || op[x] >= x) return false;
            if (creator[sp[x]] != creator[x] || seq[sp[x]] + 1 != seq[x])
                return false;
        }
        return true;
    }

    inline int32_t *la_row(int64_t x) { return &la[(size_t)x * C]; }
    inline int32_t *fd_row(int64_t x) { return &fd[(size_t)x * C]; }

    // one chain per tip (an event nobody extends), root to tip
    void build_chains() {
        std::vector<uint8_t> extended(e, 0);
        for (int64_t x = 0; x < e; ++x)
            if (sp[x] >= 0) extended[sp[x]] = 1;
        for (int64_t tip = 0; tip < e; ++tip) {
            if (extended[tip]) continue;
            std::vector<int32_t> path;
            for (int64_t x = tip; x >= 0; x = sp[x]) path.push_back((int32_t)x);
            std::reverse(path.begin(), path.end());
            for (int32_t x : path) {
                on[x].push_back(C);
                if (home[x] < 0) home[x] = C;
            }
            cr_chains[creator[tip]].push_back(C);
            chain_ev.push_back(std::move(path));
            ++C;
        }
        la.assign((size_t)e * C, -1);
        fd.assign((size_t)e * C, I32_MAX);
    }

    // insert + coordinates, events in topological order
    void insert(int64_t x) {
        int32_t *row = la_row(x);
        if (sp[x] >= 0) {
            const int32_t *ps = la_row(sp[x]), *po = la_row(op[x]);
            for (int32_t k = 0; k < C; ++k) row[k] = std::max(ps[k], po[k]);
            int64_t pref = std::max(eff[sp[x]], eff[op[x]]);
            eff[x] = std::min(std::max(ts[x], pref + 1),
                              pref + TS_CLAMP_WINDOW_NS);
        } else {
            eff[x] = ts[x];
        }
        for (int32_t c : on[x]) {
            row[c] = seq[x];
            fd_row(x)[c] = seq[x];
        }
        // UpdateAncestorFirstDescendant (hashgraph.go:466-494), for every
        // chain x lies on: walk each last-ancestor's chain down until a
        // link already has a first descendant on that chain
        for (int32_t c : on[x]) {
            for (int32_t k = 0; k < C; ++k) {
                for (int32_t s = row[k]; s >= 0; --s) {
                    int32_t *f = &fd_row(chain_ev[k][s])[c];
                    if (*f != I32_MAX) break;
                    *f = seq[x];
                }
            }
        }
    }

    // det[x, c]: x's ancestors by creator c are not one self-parent path
    // (their top event on some chain of c is not on the deepest one's)
    void detect(int64_t x) {
        if (fork_blind) return;
        const int32_t *row = la_row(x);
        for (int32_t c = 0; c < n; ++c) {
            const std::vector<int32_t> &chs = cr_chains[c];
            if (chs.size() < 2) continue;
            int32_t deep = chs[0];
            for (int32_t ch : chs)
                if (row[ch] > row[deep]) deep = ch;
            for (int32_t ch : chs) {
                int32_t p = row[ch];
                if (p >= 0 && chain_ev[ch][p] != chain_ev[deep][p]) {
                    det[(size_t)x * n + c] = 1;
                    break;
                }
            }
        }
    }

    inline bool detects(int64_t x, int32_t c) const {
        return det[(size_t)x * n + c] != 0;
    }

    // see(x, y): y is an ancestor of x, and x sees no fork by y's creator
    inline bool sees(int64_t x, int64_t y) {
        return la_row(x)[home[y]] >= seq[y] && !detects(x, creator[y]);
    }

    // strongly_see(x, y): creators c with an event w (ancestor of x,
    // descendant of y) that x sees and that sees y.  Along a chain the
    // descendants of y form a suffix and detection only grows, so the
    // chain's first descendant of y is the one to ask.
    bool strongly_sees(int64_t x, int64_t y) {
        const int32_t *lax = la_row(x), *fdy = fd_row(y);
        int32_t cy = creator[y], cnt = 0;
        for (int32_t c = 0; c < n; ++c) {
            if (detects(x, c)) continue;
            for (int32_t ch : cr_chains[c]) {
                int32_t f = fdy[ch];
                if (f == I32_MAX || f > lax[ch]) continue;
                if (detects(chain_ev[ch][f], cy)) continue;
                ++cnt;
                break;
            }
        }
        return cnt >= super_majority;
    }

    // DivideRounds: round/witness assignment in topological order
    void divide_rounds(int64_t x) {
        int32_t r = 0;
        if (sp[x] >= 0) {
            int32_t pr = std::max(round[sp[x]], round[op[x]]);
            std::vector<uint8_t> seen(n, 0);
            int32_t cnt = 0;
            if (pr < (int32_t)witnesses.size())
                for (int32_t w : witnesses[pr])
                    if (!seen[creator[w]] && strongly_sees(x, w)) {
                        seen[creator[w]] = 1;
                        ++cnt;
                    }
            r = pr + (cnt >= super_majority ? 1 : 0);
        }
        round[x] = r;
        bool wit = sp[x] < 0 || r > round[sp[x]];
        witness[x] = wit;
        if (wit) {
            if ((int32_t)witnesses.size() <= r) witnesses.resize(r + 1);
            witnesses[r].push_back((int32_t)x);
            fame[x] = 0;
        }
    }

    // DecideFame (hashgraph.go:590-673) in byzantine.py's loop order, a
    // vote tally of one strongly seen witness per creator
    void decide_fame() {
        int32_t R = (int32_t)witnesses.size();
        std::unordered_map<int64_t, bool> votes;
        auto vkey = [](int64_t y, int64_t x) { return (y << 32) | x; };
        // y -> the round[y]-1 witnesses it strongly sees, one per creator
        std::unordered_map<int64_t, std::vector<int32_t>> ss_memo;
        for (int32_t i = 0; i < R; ++i) {
            for (int32_t x : witnesses[i]) {
                if (fame[x] != 0) continue;  // sticky
                for (int32_t j = i + 1; j < R && fame[x] == 0; ++j) {
                    for (int32_t y : witnesses[j]) {
                        int32_t diff = j - i;
                        if (diff == 1) {
                            votes[vkey(y, x)] = sees(y, x);
                            continue;
                        }
                        auto it = ss_memo.find(y);
                        if (it == ss_memo.end()) {
                            std::vector<int32_t> ss;
                            std::vector<uint8_t> seen(n, 0);
                            for (int32_t w : witnesses[j - 1])
                                if (!seen[creator[w]] && strongly_sees(y, w)) {
                                    seen[creator[w]] = 1;
                                    ss.push_back(w);
                                }
                            it = ss_memo.emplace(y, std::move(ss)).first;
                        }
                        int32_t yays = 0;
                        for (int32_t w : it->second) {
                            auto v = votes.find(vkey(w, x));
                            if (v != votes.end() && v->second) ++yays;
                        }
                        int32_t nays = (int32_t)it->second.size() - yays;
                        bool v = yays >= nays;
                        int32_t t = v ? yays : nays;
                        if (diff % n > 0) {  // normal round
                            if (t >= super_majority) {
                                fame[x] = v ? 1 : 2;
                                break;
                            }
                            votes[vkey(y, x)] = v;
                        } else {             // coin round
                            votes[vkey(y, x)] =
                                t >= super_majority ? v : mbit[y] != 0;
                        }
                    }
                }
            }
        }
    }

    // DecideRoundReceived + median consensus timestamps
    // (hashgraph.go:676-721, 762-770)
    void decide_order() {
        int32_t R = (int32_t)witnesses.size();
        std::vector<uint8_t> decided(R, 0);
        std::vector<std::vector<int32_t>> famous(R);
        for (int32_t r = 0; r < R; ++r) {
            bool all = true;
            for (int32_t w : witnesses[r]) {
                if (fame[w] == 0) all = false;
                else if (fame[w] == 1) famous[r].push_back(w);
            }
            decided[r] = all && !witnesses[r].empty();
        }
        std::vector<int64_t> med;
        for (int64_t x = 0; x < e; ++x) {
            for (int32_t i = round[x] + 1; i < R; ++i) {
                if (!decided[i]) continue;  // skip, not break
                med.clear();
                for (int32_t w : famous[i])
                    if (sees(w, x)) {
                        // oldest self-ancestor of w to see x: w's chain
                        // event at x's first descendant there
                        int32_t c = home[w];
                        med.push_back(eff[chain_ev[c][fd_row(x)[c]]]);
                    }
                if ((int32_t)med.size() * 2 > (int32_t)famous[i].size()) {
                    rr[x] = i;
                    std::sort(med.begin(), med.end());
                    if (ts_rule == 1) {
                        // CONTROL: mean of the first-seen times, taken
                        // as offsets from the smallest (no overflow)
                        __int128 acc = 0;
                        for (int64_t t : med) acc += t - med[0];
                        cts[x] = med[0] + (int64_t)(acc / (int64_t)med.size());
                    } else {
                        cts[x] = med[med.size() / 2];
                    }
                    break;
                }
            }
        }
    }

    int64_t run() {
        build_chains();
        for (int64_t x = 0; x < e; ++x) {
            insert(x);
            detect(x);
        }
        for (int64_t x = 0; x < e; ++x) divide_rounds(x);
        decide_fame();
        decide_order();
        int64_t ordered = 0;
        for (int64_t x = 0; x < e; ++x) ordered += (rr[x] >= 0);
        return ordered;
    }
};

}  // namespace

extern "C" {

// Runs the fork-aware reference over a topologically ordered
// struct-of-arrays DAG.  Outputs are caller-allocated [e] arrays.
// Returns the number of events brought to consensus order, or -1 on a
// refused input.
int64_t fork_reference_consensus(
    int32_t n, int64_t e, int32_t ts_rule, int32_t fork_blind,
    const int32_t *sp, const int32_t *op, const int32_t *creator,
    const int32_t *seq, const int64_t *ts, const uint8_t *mbit,
    int32_t *round_out, uint8_t *witness_out, int32_t *rr_out,
    int64_t *cts_out, int8_t *fame_out
) {
    if (n <= 0 || e <= 0) return -1;
    if ((ts_rule != 0 && ts_rule != 1) || (fork_blind != 0 && fork_blind != 1))
        return -1;
    ForkReference f(n, e, sp, op, creator, seq, ts, mbit, ts_rule, fork_blind);
    if (!f.valid()) return -1;
    int64_t ordered = f.run();
    std::memcpy(round_out, f.round.data(), sizeof(int32_t) * e);
    std::memcpy(witness_out, f.witness.data(), sizeof(uint8_t) * e);
    std::memcpy(rr_out, f.rr.data(), sizeof(int32_t) * e);
    std::memcpy(cts_out, f.cts.data(), sizeof(int64_t) * e);
    std::memcpy(fame_out, f.fame.data(), sizeof(int8_t) * e);
    return ordered;
}

}  // extern "C"
