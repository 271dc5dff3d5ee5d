// Native segment-build hot loops.
//
// The TPU answers queries; the HOST builds segments — and the build's hot
// loops (cube grouping, grouped stats, fixed-bit packing) are pure
// pointer-chasing/accumulation work where numpy pays a full array pass
// per operator. This is the same division of labor as the reference,
// whose segment creation is native Java/C++ speed
// (core/segment/creator/impl/SegmentIndexCreationDriverImpl.java): one
// tight loop per task, compiled -O3, called through ctypes.
//
// Build: compiled on first use by pinot_tpu/native/__init__.py with g++
// (graceful numpy fallback when no compiler is present).

#include <cstdint>
#include <cstring>
#include <algorithm>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// pack_bits: ids (< 2^nb) -> dense little-endian bitstream as uint32 words
// ---------------------------------------------------------------------------
void pack_bits_u32(const int32_t* ids, int64_t n, int nb, uint32_t* out,
                   int64_t n_words) {
    std::memset(out, 0, n_words * sizeof(uint32_t));
    uint64_t acc = 0;      // bit accumulator, low bits first
    int fill = 0;          // bits currently in acc
    int64_t w = 0;
    for (int64_t i = 0; i < n; ++i) {
        acc |= (uint64_t)(uint32_t)ids[i] << fill;
        fill += nb;
        while (fill >= 32) {
            out[w++] = (uint32_t)acc;
            acc >>= 32;
            fill -= 32;
        }
    }
    if (fill > 0 && w < n_words) out[w] = (uint32_t)acc;
}

// inverse of pack_bits_u32: dense little-endian bitstream -> ids
void unpack_bits_u32(const uint32_t* words, int64_t n_words, int nb,
                     int64_t n, int32_t* out) {
    uint64_t acc = 0;
    int fill = 0;
    int64_t w = 0;
    const uint32_t mask = (nb >= 32) ? 0xFFFFFFFFu
                                     : ((1u << nb) - 1u);
    for (int64_t i = 0; i < n; ++i) {
        while (fill < nb && w < n_words) {
            acc |= (uint64_t)words[w++] << fill;
            fill += 32;
        }
        out[i] = (int32_t)(acc & mask);
        acc >>= nb;
        fill -= nb;
    }
}

// ---------------------------------------------------------------------------
// group_index_i64: row keys -> per-row group ranks (sorted-key order) +
// sorted unique keys. Open-addressing hash (splitmix64 mix), then the
// unique set (tiny vs n) is sorted and ranks remapped.
// Returns g (number of groups), or -1 on alloc failure.
// ---------------------------------------------------------------------------
static inline uint64_t mix64(uint64_t x) {
    x += 0x9e3779b97f4a7c15ULL;
    x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
    x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
    return x ^ (x >> 31);
}

int64_t group_index_i64(const int64_t* key, int64_t n,
                        int64_t* uniq_out, int32_t* rank_out) {
    if (n <= 0) return 0;
    uint64_t cap = 1;
    while (cap < (uint64_t)n * 2) cap <<= 1;
    std::vector<int64_t> tkey;
    std::vector<int32_t> tgid;
    try {
        tkey.assign(cap, INT64_MIN);     // INT64_MIN = empty sentinel
        tgid.assign(cap, -1);
    } catch (...) { return -1; }
    const uint64_t mask = cap - 1;
    int64_t ng = 0;
    // pass 1: assign provisional group ids in first-seen order
    for (int64_t i = 0; i < n; ++i) {
        int64_t k = key[i];
        uint64_t h = mix64((uint64_t)k) & mask;
        for (;;) {
            if (tkey[h] == k) { rank_out[i] = tgid[h]; break; }
            if (tkey[h] == INT64_MIN) {
                tkey[h] = k;
                tgid[h] = (int32_t)ng;
                uniq_out[ng] = k;
                rank_out[i] = (int32_t)ng;
                ++ng;
                break;
            }
            h = (h + 1) & mask;
        }
    }
    // sort unique keys, remap provisional ids -> sorted ranks
    std::vector<int32_t> order((size_t)ng);
    for (int64_t i = 0; i < ng; ++i) order[i] = (int32_t)i;
    std::sort(order.begin(), order.end(),
              [&](int32_t a, int32_t b) { return uniq_out[a] < uniq_out[b]; });
    std::vector<int32_t> rank_of((size_t)ng);
    std::vector<int64_t> sorted((size_t)ng);
    for (int64_t r = 0; r < ng; ++r) {
        rank_of[order[r]] = (int32_t)r;
        sorted[r] = uniq_out[order[r]];
    }
    std::memcpy(uniq_out, sorted.data(), (size_t)ng * sizeof(int64_t));
    for (int64_t i = 0; i < n; ++i) rank_out[i] = rank_of[rank_out[i]];
    return ng;
}

// ---------------------------------------------------------------------------
// grouped stats: one pass accumulating count/sum/min/max per group
// ---------------------------------------------------------------------------
void group_counts_i64(const int32_t* rank, int64_t n, int64_t g,
                      int64_t* counts) {
    std::memset(counts, 0, (size_t)g * sizeof(int64_t));
    for (int64_t i = 0; i < n; ++i) counts[rank[i]]++;
}

void group_stats_f64(const int32_t* rank, const double* vals, int64_t n,
                     int64_t g, double* sums, double* mins, double* maxs) {
    for (int64_t j = 0; j < g; ++j) {
        sums[j] = 0.0;
        mins[j] = 1e308 * 10;            // +inf
        maxs[j] = -1e308 * 10;           // -inf
    }
    for (int64_t i = 0; i < n; ++i) {
        int32_t r = rank[i];
        double v = vals[i];
        sums[r] += v;
        if (v < mins[r]) mins[r] = v;
        if (v > maxs[r]) maxs[r] = v;
    }
}

// grouped stats over an argsort permutation: one pass fusing the gather
// (vals[order]) with sum/min/max accumulation per run — replaces a 64MB
// materialized gather plus three reduceat passes
void group_stats_sorted_f64(const int64_t* order, const int64_t* starts,
                            int64_t g, int64_t n, const double* vals,
                            double* sums, double* mins, double* maxs) {
    for (int64_t j = 0; j < g; ++j) {
        int64_t e = (j + 1 < g) ? starts[j + 1] : n;
        double s = 0.0, mn = 1e308 * 10, mx = -1e308 * 10;
        for (int64_t i = starts[j]; i < e; ++i) {
            double v = vals[order[i]];
            s += v;
            if (v < mn) mn = v;
            if (v > mx) mx = v;
        }
        sums[j] = s;
        mins[j] = mn;
        maxs[j] = mx;
    }
}

// mixed-radix packed key construction: key = ((d0*c1)+d1)*c2+d2 ... in one
// pass (numpy pays 2 full passes per dimension)
void packed_key_i64(const int32_t* const* dims, const int64_t* cards,
                    int n_dims, int64_t n, int64_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        int64_t k = 0;
        for (int d = 0; d < n_dims; ++d) k = k * cards[d] + dims[d][i];
        out[i] = k;
    }
}

// ---------------------------------------------------------------------------
// cube_select_gather: one query's star-tree descent over S segments, the
// one entry point here on the QUERY path (startree/executor.py). Cube rows
// are sorted by the split order, so the leading dimensions' dictId
// intervals narrow to row blocks by binary search, level after level; the
// rows of the surviving blocks are tested against the residual leaves
// (interval lists over their own dimension lanes) and the matched rows'
// group codes, counts and stat lanes are written out segment-major, rows
// ascending: what the numpy twin's gathers and concatenations yield.
// Literal semantics stay in Python: this sees ids and intervals only.
//
// All tables are int64, pointers included:
//   seg_hdr [S][4]     n_groups, counts*, n_levels, n_resid
//   preds   [P][3]     lane* (int32), first and one-past-last row of `ivs`;
//                      segment-major, a segment's levels then its residuals
//   ivs     [I][2]     dictId interval [lo, hi)
//   gcols   [S][G][3]  lane* (int32), lut* (int64 local id -> group code;
//                      0 = the id itself), lut length
//   stats   [S][K]     stat lane* (float64)
//   out_codes [G][cap], out_counts [cap], out_stats [K][cap],
//   out_seg [S][2]     rows matched, rows examined
// Returns the rows written; -1 where a level would pass block_limit (the
// stepwise twin stops descending there and scans); -2 where the blocks hold
// more than `cap` rows (out_seg then carries every segment's examined
// count, nothing else is written); -3 on a lane id outside its lut or an
// allocation failure.
// ---------------------------------------------------------------------------
static inline bool in_intervals(int64_t id, const int64_t* ivs,
                                int64_t first, int64_t last) {
    for (int64_t i = first; i < last; ++i)
        if (id >= ivs[2 * i] && id < ivs[2 * i + 1]) return true;
    return false;
}

int64_t cube_select_gather(
        int64_t n_seg, const int64_t* seg_hdr, const int64_t* preds,
        const int64_t* ivs, const int64_t* gcols, int64_t n_gcols,
        const int64_t* stats, int64_t n_stats, int64_t block_limit,
        int64_t cap, int64_t* out_codes, int64_t* out_counts,
        double* out_stats, int64_t* out_seg) {
    typedef std::pair<int64_t, int64_t> Block;
    try {
        // pass 1: the descent — every segment's row blocks
        std::vector<std::vector<Block>> seg_blocks((size_t)n_seg);
        std::vector<int64_t> first_pred((size_t)n_seg);
        std::vector<Block> next;
        int64_t p = 0, examined_all = 0;
        for (int64_t s = 0; s < n_seg; ++s) {
            const int64_t* h = seg_hdr + 4 * s;
            std::vector<Block>& blocks = seg_blocks[s];
            blocks.push_back(Block(0, h[0]));
            first_pred[s] = p;
            for (int64_t l = 0; l < h[2] && !blocks.empty(); ++l) {
                const int64_t* pr = preds + 3 * (p + l);
                const int32_t* lane = (const int32_t*)(intptr_t)pr[0];
                int64_t n_iv = pr[2] - pr[1];
                if ((int64_t)blocks.size() * std::max<int64_t>(n_iv, 1)
                        > block_limit)
                    return -1;
                next.clear();
                for (const Block& b : blocks)
                    for (int64_t i = pr[1]; i < pr[2]; ++i) {
                        const int32_t* lo = std::lower_bound(
                            lane + b.first, lane + b.second, ivs[2 * i],
                            [](int32_t v, int64_t k) { return v < k; });
                        const int32_t* hi = std::lower_bound(
                            lo, lane + b.second, ivs[2 * i + 1],
                            [](int32_t v, int64_t k) { return v < k; });
                        if (lo < hi) next.push_back(Block(lo - lane,
                                                          hi - lane));
                    }
                blocks.swap(next);
            }
            p += h[2] + h[3];
            int64_t examined = 0;
            for (const Block& b : blocks) examined += b.second - b.first;
            out_seg[2 * s] = 0;
            out_seg[2 * s + 1] = examined;
            examined_all += examined;
        }
        if (examined_all > cap) return -2;

        // pass 2: residual leaves, then the gather of what matched
        int64_t m = 0;
        for (int64_t s = 0; s < n_seg; ++s) {
            const int64_t* h = seg_hdr + 4 * s;
            const int64_t* counts = (const int64_t*)(intptr_t)h[1];
            const int64_t* resid = preds + 3 * (first_pred[s] + h[2]);
            const int64_t* gc = gcols + 3 * n_gcols * s;
            const int64_t* st = stats + n_stats * s;
            const int64_t m0 = m;
            for (const Block& b : seg_blocks[s])
                for (int64_t r = b.first; r < b.second; ++r) {
                    bool keep = true;
                    for (int64_t q = 0; q < h[3] && keep; ++q) {
                        const int64_t* pr = resid + 3 * q;
                        keep = in_intervals(
                            ((const int32_t*)(intptr_t)pr[0])[r], ivs,
                            pr[1], pr[2]);
                    }
                    if (!keep) continue;
                    for (int64_t g = 0; g < n_gcols; ++g) {
                        int64_t id =
                            ((const int32_t*)(intptr_t)gc[3 * g])[r];
                        if (gc[3 * g + 1]) {
                            if (id < 0 || id >= gc[3 * g + 2]) return -3;
                            id = ((const int64_t*)(intptr_t)
                                  gc[3 * g + 1])[id];
                        }
                        out_codes[g * cap + m] = id;
                    }
                    out_counts[m] = counts[r];
                    for (int64_t k = 0; k < n_stats; ++k)
                        out_stats[k * cap + m] =
                            ((const double*)(intptr_t)st[k])[r];
                    ++m;
                }
            out_seg[2 * s] = m - m0;
        }
        return m;
    } catch (...) { return -3; }
}

}  // extern "C"
