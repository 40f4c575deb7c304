// Native host-side kernels for the tree/metadata passes.
//
// The reference keeps its hot host-side machinery in compiled code
// (Fortran tree walks amr/nbors_utils.f90, C++/CUDA atonlib, pario
// transfer.c); these are the equivalents for our host core: space-filling
// curve keys, batched ordered lookups, and neighbour index-map
// construction — the build_comm-shaped passes that run after each
// refinement (SURVEY.md §7).
//
// Hilbert indices use John Skilling's public-domain transpose algorithm
// ("Programming the Hilbert curve", AIP Conf. Proc. 707, 381 (2004)) —
// an independent, cleaner formulation of what amr/hilbert.f90 implements
// with per-dimension state machines.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

extern "C" {

// ---------------------------------------------------------------- Morton
static inline uint64_t spread2(uint64_t x) {
    x &= 0xFFFFFFFFull;
    x = (x | (x << 16)) & 0x0000FFFF0000FFFFull;
    x = (x | (x << 8))  & 0x00FF00FF00FF00FFull;
    x = (x | (x << 4))  & 0x0F0F0F0F0F0F0F0Full;
    x = (x | (x << 2))  & 0x3333333333333333ull;
    x = (x | (x << 1))  & 0x5555555555555555ull;
    return x;
}

static inline uint64_t spread3(uint64_t x) {
    x &= 0x1FFFFFull;
    x = (x | (x << 32)) & 0x1F00000000FFFFull;
    x = (x | (x << 16)) & 0x1F0000FF0000FFull;
    x = (x | (x << 8))  & 0x100F00F00F00F00Full;
    x = (x | (x << 4))  & 0x10C30C30C30C30C3ull;
    x = (x | (x << 2))  & 0x1249249249249249ull;
    return x;
}

void morton_encode(const int64_t* og, int64_t n, int ndim, int64_t* out) {
    if (ndim == 1) {
        memcpy(out, og, sizeof(int64_t) * (size_t)n);
    } else if (ndim == 2) {
        for (int64_t i = 0; i < n; i++)
            out[i] = (int64_t)(spread2((uint64_t)og[2 * i])
                               | (spread2((uint64_t)og[2 * i + 1]) << 1));
    } else {
        for (int64_t i = 0; i < n; i++)
            out[i] = (int64_t)(spread3((uint64_t)og[3 * i])
                               | (spread3((uint64_t)og[3 * i + 1]) << 1)
                               | (spread3((uint64_t)og[3 * i + 2]) << 2));
    }
}

// ---------------------------------------------------------------- Hilbert
// Skilling (2004): AxesToTranspose + bit interleave of the transpose.
static inline uint64_t hilbert_key_one(uint64_t* X, int b, int n) {
    uint64_t M = 1ull << (b - 1), P, Q, t;
    // Inverse undo
    for (Q = M; Q > 1; Q >>= 1) {
        P = Q - 1;
        for (int i = 0; i < n; i++) {
            if (X[i] & Q) X[0] ^= P;
            else { t = (X[0] ^ X[i]) & P; X[0] ^= t; X[i] ^= t; }
        }
    }
    // Gray encode
    for (int i = 1; i < n; i++) X[i] ^= X[i - 1];
    t = 0;
    for (Q = M; Q > 1; Q >>= 1)
        if (X[n - 1] & Q) t ^= Q - 1;
    for (int i = 0; i < n; i++) X[i] ^= t;
    // interleave transpose bits, x-bit most significant per group
    uint64_t key = 0;
    for (int j = b - 1; j >= 0; j--)
        for (int i = 0; i < n; i++)
            key = (key << 1) | ((X[i] >> j) & 1ull);
    return key;
}

void hilbert_encode(const int64_t* og, int64_t n, int ndim, int nbits,
                    uint64_t* out) {
    uint64_t X[3];
    for (int64_t i = 0; i < n; i++) {
        for (int d = 0; d < ndim; d++)
            X[d] = (uint64_t)og[i * ndim + d];
        out[i] = hilbert_key_one(X, nbits, ndim);
    }
}

// ------------------------------------------------------------- searching
void searchsorted_i64(const int64_t* sorted, int64_t m, const int64_t* q,
                      int64_t n, int64_t* out) {
    for (int64_t i = 0; i < n; i++) {
        int64_t lo = 0, hi = m;
        int64_t v = q[i];
        while (lo < hi) {
            int64_t mid = (lo + hi) >> 1;
            if (sorted[mid] < v) lo = mid + 1;
            else hi = mid;
        }
        out[i] = lo;
    }
}

// lookup: position where sorted[pos]==q, else -1
void lookup_i64(const int64_t* sorted, int64_t m, const int64_t* q,
                int64_t n, int64_t* out) {
    for (int64_t i = 0; i < n; i++) {
        int64_t lo = 0, hi = m;
        int64_t v = q[i];
        while (lo < hi) {
            int64_t mid = (lo + hi) >> 1;
            if (sorted[mid] < v) lo = mid + 1;
            else hi = mid;
        }
        out[i] = (lo < m && sorted[lo] == v) ? lo : -1;
    }
}

// ------------------------------------------------- neighbour index maps
// For each oct (og[i]) and each offset (offs[k]), find the index of the
// oct at og[i]+offs[k] (periodic wrap at level_size) in the sorted key
// array; -1 if absent.  This is the kernel of build_level_maps — the
// get3cubefather equivalent (amr/nbors_utils.f90:5).
void neighbor_lookup(const int64_t* keys_sorted, const int64_t* og,
                     int64_t noct, int ndim, int64_t level_size,
                     const int64_t* offs, int64_t nofs, int64_t* out) {
    uint64_t tmp[3];
    for (int64_t i = 0; i < noct; i++) {
        for (int64_t k = 0; k < nofs; k++) {
            // wrapped neighbour coordinates → Morton key
            for (int d = 0; d < ndim; d++) {
                int64_t c = og[i * ndim + d] + offs[k * ndim + d];
                c %= level_size;
                if (c < 0) c += level_size;
                tmp[d] = (uint64_t)c;
            }
            uint64_t key;
            if (ndim == 1) key = tmp[0];
            else if (ndim == 2)
                key = spread2(tmp[0]) | (spread2(tmp[1]) << 1);
            else
                key = spread3(tmp[0]) | (spread3(tmp[1]) << 1)
                    | (spread3(tmp[2]) << 2);
            // binary search
            int64_t lo = 0, hi = noct;
            int64_t v = (int64_t)key;
            while (lo < hi) {
                int64_t mid = (lo + hi) >> 1;
                if (keys_sorted[mid] < v) lo = mid + 1;
                else hi = mid;
            }
            out[i * nofs + k] =
                (lo < noct && keys_sorted[lo] == v) ? lo : -1;
        }
    }
}

}  // extern "C"

// ------------------------------------------------- blocked tile tables
// One pass per partial level over its Morton TILES (aligned cubes of
// 2^shift octs a side, each with a 2-cell halo: a box of td = 2^(shift+1)
// + 4 cells a side) that writes every table of amr/maps.py::BlockMaps —
// what build_block_maps' numpy path makes by materialising each quantity
// for every slot of every tile as an int64 array.  Element for element
// the same tables (tests/test_native.py holds the two together).
//
// What makes one pass enough: the box starts on an even cell and the
// level's extents are even, so the two cells of a pair along an axis are
// BC-mapped into ONE oct (periodic wrap, mirror and clamp alike).
// Existence, the row of the covering oct, the radius-2 dilation (= one
// oct on the box's (td/2)^ndim oct grid) and "missed" are therefore
// properties of the box's OCTS; the missed cells of one oct are
// consecutive Morton keys, so sorting the missed OCT keys orders the
// interpolation rows (np.unique's order), and the father cell and its
// 2*ndim neighbours are shared by the cells of an oct.
//
// tile_plan does the tree-dependent work at oct granularity and returns
// the counts the caller buckets (ntile, ni); tile_emit expands the plan
// to slots straight into the caller's padded arrays; tile_free drops it.

namespace {

struct TilePlan {
    int ndim, shift, c, td, nb;          // nb = td/2: octs a box side
    int64_t nslot, nobox;
    int64_t dims[3];
    int bc[6];
    bool has_coarse;
    int64_t ntile, ni, nmiss_father;
    bool any_refl;
    std::vector<int64_t> tile_key, tile_start;   // [ntile], [ntile+1]
    std::vector<int32_t> row;     // [ntile*nobox] covering oct row or -1
    std::vector<uint8_t> okmask;  // [ntile*nobox] refined children (Morton)
    std::vector<int32_t> uidx;    // [ntile*nobox] missed-oct rank or -1
    std::vector<int32_t> ubase;   // [nu] first interpolation row
    std::vector<uint8_t> umask;   // [nu] requested children (Morton bits)
    std::vector<int32_t> ucell;   // [nu] father cell at l-1
    std::vector<int32_t> unb;     // [nu*ndim*2] its neighbours
};

inline uint64_t spread_n(uint64_t x, int ndim) {
    return ndim == 1 ? x : ndim == 2 ? spread2(x) : spread3(x);
}

inline uint64_t compact_n(uint64_t k, int ndim) {
    if (ndim == 1) return k;
    if (ndim == 2) {
        k &= 0x5555555555555555ull;
        k = (k | (k >> 1))  & 0x3333333333333333ull;
        k = (k | (k >> 2))  & 0x0F0F0F0F0F0F0F0Full;
        k = (k | (k >> 4))  & 0x00FF00FF00FF00FFull;
        k = (k | (k >> 8))  & 0x0000FFFF0000FFFFull;
        k = (k | (k >> 16)) & 0x00000000FFFFFFFFull;
        return k;
    }
    k &= 0x1249249249249249ull;
    k = (k | (k >> 2))  & 0x10C30C30C30C30C3ull;
    k = (k | (k >> 4))  & 0x100F00F00F00F00Full;
    k = (k | (k >> 8))  & 0x1F0000FF0000FFull;
    k = (k | (k >> 16)) & 0x1F00000000FFFFull;
    k = (k | (k >> 32)) & 0x1FFFFFull;
    return k;
}

// tree.map_coords on one coordinate: periodic wrap, reflecting mirror
// (refl set), outflow clamp; the final clip as numpy's
inline int64_t map_coord(int64_t x, int64_t n, int lo, int hi, bool* refl) {
    *refl = false;
    if (lo == 0 && hi == 0) {
        int64_t m = x % n;
        return m < 0 ? m + n : m;
    }
    const bool below = x < 0, above = x >= n;
    int64_t m = x;
    if (lo == 1) { if (below) { m = -1 - x; *refl = true; } }
    else if (lo != 0) { if (below) m = 0; }
    if (hi == 1) { if (above) { m = 2 * n - 1 - m; *refl = true; } }
    else if (hi != 0) { if (above) m = n - 1; }
    return m < 0 ? 0 : (m > n - 1 ? n - 1 : m);
}

// position of v in the sorted keys, -1 if absent.  A complete level on
// a cubic root holds the keys 0..n-1, where the key IS the position; any
// other level gets an open-addressing table (Fibonacci hashing, linear
// probing, load <= 1/2) built once a pass: a tile's box asks for ~6^ndim
// octs, most of them present, and a binary search of 10^4 keys is 13-14
// unpredictable branches each
struct Level {
    const int64_t* k;
    int64_t n;
    bool direct;
    int hshift;
    std::vector<int32_t> tab;
    Level(const int64_t* keys, int64_t len)
        : k(keys), n(len), direct(len > 0 && keys[len - 1] == len - 1),
          hshift(0) {
        if (direct || n == 0) return;
        int bits = 4;
        while ((1ll << bits) < 2 * n) bits++;
        hshift = 64 - bits;
        tab.assign((size_t)1 << bits, -1);
        const size_t mask = tab.size() - 1;
        for (int64_t i = 0; i < n; i++) {
            size_t h = slot(k[i]);
            while (tab[h] >= 0) h = (h + 1) & mask;
            tab[h] = (int32_t)i;
        }
    }
    size_t slot(int64_t v) const {
        return (size_t)(((uint64_t)v * 0x9E3779B97F4A7C15ull) >> hshift);
    }
    int64_t find(int64_t v) const {
        if (direct) return (v >= 0 && v < n) ? v : -1;
        if (n == 0) return -1;
        const size_t mask = tab.size() - 1;
        for (size_t h = slot(v);; h = (h + 1) & mask) {
            const int32_t i = tab[h];
            if (i < 0) return -1;
            if (k[i] == v) return i;
        }
    }
};

// Morton low-bit pattern (x at bit 0) -> flat cell offset (x slowest)
inline void flat_off_table(int ndim, int32_t* out) {
    for (int m = 0; m < (1 << ndim); m++) {
        int f = 0;
        for (int d = 0; d < ndim; d++) f = f * 2 + ((m >> d) & 1);
        out[m] = f;
    }
}

// per-axis slot geometry of one tile: the BC-mapped coordinate of each of
// the td positions along axis d as its key bits (s) and its reflection
// bit (r); a slot's cell key is the OR of its three s, its vbits of its r
struct AxisTabs {
    int td;
    std::vector<int64_t> s;
    std::vector<uint8_t> r;
    explicit AxisTabs(int td_) : td(td_), s(3 * td_, 0), r(3 * td_, 0) {}
    bool fill(const TilePlan& P, int64_t tkey) {
        bool any = false;
        const uint64_t okey0 = (uint64_t)tkey << (P.ndim * P.shift);
        for (int d = 0; d < P.ndim; d++) {
            const int64_t org = 2 * (int64_t)compact_n(okey0 >> d, P.ndim);
            for (int p = 0; p < td; p++) {
                bool rf;
                int64_t m = map_coord(org + p - 2, P.dims[d], P.bc[2 * d],
                                      P.bc[2 * d + 1], &rf);
                s[d * td + p] = (int64_t)(spread_n((uint64_t)m, P.ndim) << d);
                r[d * td + p] = (uint8_t)(rf ? (1u << d) : 0u);
                any |= rf;
            }
        }
        return any;
    }
};

// loop extents over (a0, a1, a2) with the axes a tile lacks held to 1:
// loop level k is dimension k - (3 - ndim)
inline void extents(int ndim, int n, int e[3]) {
    for (int k = 0; k < 3; k++) e[k] = (k >= 3 - ndim) ? n : 1;
}

// strides of the box's oct grid over the loop levels (d = 0 slowest)
inline void box_strides(int ndim, int nb, int64_t st[3]) {
    int64_t v = 1;
    for (int k = 2; k >= 0; k--) {
        st[k] = k >= 3 - ndim ? v : 0;
        if (k >= 3 - ndim) v *= nb;
    }
}

struct MissEnt { int64_t okey; int32_t id; uint8_t mask; };

}  // namespace

extern "C" {

// counts: [ntile, ni, any_refl, missing fathers]
void* tile_plan(const int64_t* keys_m1, int64_t n_m1,
                const int64_t* keys_l, int64_t n_l,
                const int64_t* keys_p1, int64_t n_p1,
                int ndim, int shift, const int64_t* dims, const int64_t* bc,
                int has_coarse, int64_t* counts) {
    TilePlan* Pp = new TilePlan();
    TilePlan& P = *Pp;
    P.ndim = ndim; P.shift = shift;
    P.c = 1 << (shift + 1); P.td = P.c + 4; P.nb = P.td / 2;
    P.nslot = 1; P.nobox = 1;
    for (int d = 0; d < ndim; d++) { P.nslot *= P.td; P.nobox *= P.nb; }
    for (int d = 0; d < 3; d++) P.dims[d] = d < ndim ? dims[d] : 1;
    for (int i = 0; i < 6; i++) P.bc[i] = i < 2 * ndim ? (int)bc[i] : 0;
    P.has_coarse = has_coarse != 0;
    const int ttd = 1 << ndim;
    const int64_t cm = ttd - 1;
    const int tbits = ndim * shift;

    // 1. tiles: runs of equal key >> tbits in the sorted level keys
    for (int64_t i = 0; i < n_l; i++) {
        int64_t t = keys_l[i] >> tbits;
        if (P.tile_key.empty() || P.tile_key.back() != t) {
            P.tile_key.push_back(t);
            P.tile_start.push_back(i);
        }
    }
    P.tile_start.push_back(n_l);
    P.ntile = (int64_t)P.tile_key.size();

    const Level L(keys_l, n_l), Lm(keys_m1, P.has_coarse ? n_m1 : 0);
    // refined children of each oct of the level (Morton bits): a cell's
    // key at l IS its covering oct's key at l+1, so one merge of the two
    // sorted key lists marks them all
    std::vector<uint8_t> rowmask(n_l, 0);
    for (int64_t i = 0, j = 0; j < n_p1; j++) {
        const int64_t okey = keys_p1[j] >> ndim;
        while (i < n_l && keys_l[i] < okey) i++;
        if (i < n_l && keys_l[i] == okey)
            rowmask[i] |= (uint8_t)(1u << (keys_p1[j] & cm));
    }
    const int64_t ntot = P.ntile * P.nobox;
    P.row.assign(ntot, -1);
    P.okmask.assign(ntot, 0);
    P.uidx.assign(ntot, -1);
    P.any_refl = false;

    AxisTabs T(P.td);
    const int td = P.td, nb = P.nb;
    int eo[3];
    extents(ndim, nb, eo);
    const int k0 = 3 - ndim;     // loop level of dimension 0
    int64_t ostr[3];
    box_strides(ndim, nb, ostr);
    std::vector<uint8_t> ex(P.nobox), nr(P.nobox), tmp(P.nobox);
    std::vector<int64_t> okeys(P.nobox);
    std::vector<MissEnt> missed;

    // 2.-3. per tile, per OCT of its box: covering oct row, refined
    // children, and the missed octs inside the influence radius
    for (int64_t t = 0; t < P.ntile; t++) {
        P.any_refl |= T.fill(P, P.tile_key[t]);
        const int64_t* s = T.s.data();
        const int64_t base = t * P.nobox;
        int64_t o = 0;
        for (int q0 = 0; q0 < eo[0]; q0++)
        for (int q1 = 0; q1 < eo[1]; q1++)
        for (int q2 = 0; q2 < eo[2]; q2++, o++) {
            const int q[3] = {q0, q1, q2};
            int64_t ck = 0;
            for (int d = 0; d < ndim; d++) ck |= s[d * td + 2 * q[k0 + d]];
            const int64_t okey = ck >> ndim;
            okeys[o] = okey;
            const int64_t r = L.find(okey);
            P.row[base + o] = (int32_t)r;
            ex[o] = r >= 0;
            if (r >= 0) P.okmask[base + o] = rowmask[r];
        }
        if (!P.has_coarse) continue;
        // Chebyshev radius 2 in cells = radius 1 on the box's oct grid,
        // zero beyond the box (maps._dilate2), one axis after the other
        nr = ex;
        for (int k = 2; k >= k0; k--) {
            tmp = nr;
            const int64_t st = ostr[k];
            int64_t i = 0;
            for (int q0 = 0; q0 < eo[0]; q0++)
            for (int q1 = 0; q1 < eo[1]; q1++)
            for (int q2 = 0; q2 < eo[2]; q2++, i++) {
                const int p = k == 0 ? q0 : k == 1 ? q1 : q2;
                nr[i] |= (uint8_t)((p > 0 && tmp[i - st])
                                   | (p < nb - 1 && tmp[i + st]));
            }
        }
        o = 0;
        for (int q0 = 0; q0 < eo[0]; q0++)
        for (int q1 = 0; q1 < eo[1]; q1++)
        for (int q2 = 0; q2 < eo[2]; q2++, o++) {
            if (ex[o] || !nr[o]) continue;
            const int q[3] = {q0, q1, q2};
            uint8_t mk = 0;
            for (int b = 0; b < ttd; b++) {
                int64_t ck = 0;
                for (int d = 0; d < ndim; d++)
                    ck |= s[d * td + 2 * q[k0 + d] + ((b >> d) & 1)];
                mk |= (uint8_t)(1u << (ck & cm));
            }
            missed.push_back({okeys[o], (int32_t)(base + o), mk});
        }
    }

    // 4. the distinct missed octs in key order; their cells, children in
    // Morton order, are the interpolation rows
    std::sort(missed.begin(), missed.end(),
              [](const MissEnt& a, const MissEnt& b) {
                  return a.okey < b.okey; });
    std::vector<int64_t> ukey;
    for (const MissEnt& e : missed) {
        if (ukey.empty() || ukey.back() != e.okey) {
            ukey.push_back(e.okey);
            P.umask.push_back(0);
        }
        P.umask.back() |= e.mask;
        P.uidx[e.id] = (int32_t)(ukey.size() - 1);
    }
    const int64_t nu = (int64_t)ukey.size();
    P.ubase.resize(nu);
    P.ucell.resize(nu);
    P.unb.resize(nu * ndim * 2);
    P.ni = 0; P.nmiss_father = 0;
    int32_t foff[8];
    flat_off_table(ndim, foff);
    const uint64_t ax0 = ndim == 1 ? ~0ull : ndim == 2
        ? 0x5555555555555555ull : 0x1249249249249249ull;
    for (int64_t u = 0; u < nu; u++) {
        const int npop = __builtin_popcount(P.umask[u]);
        P.ubase[u] = (int32_t)P.ni;
        P.ni += npop;
        // a missed oct of level l is a CELL of level l-1 (same key)
        const int64_t ckey = ukey[u];
        const int64_t f_oct = Lm.find(ckey >> ndim);
        if (f_oct < 0) P.nmiss_father += npop;
        const int64_t icell = f_oct * ttd + foff[ckey & cm];
        P.ucell[u] = (int32_t)icell;
        for (int d = 0; d < ndim; d++) {
            const int64_t x = (int64_t)compact_n((uint64_t)ckey >> d, ndim);
            for (int side = 0; side < 2; side++) {
                bool rf;
                const int64_t m = map_coord(x + (side ? 1 : -1),
                                            P.dims[d] >> 1, P.bc[2 * d],
                                            P.bc[2 * d + 1], &rf);
                const int64_t nkey = (int64_t)(
                    ((uint64_t)ckey & ~(ax0 << d))
                    | (spread_n((uint64_t)m, ndim) << d));
                const int64_t n_oct = Lm.find(nkey >> ndim);
                // absent at l-1 or mirrored: the centre cell
                P.unb[(u * ndim + d) * 2 + side] = (int32_t)(
                    (n_oct < 0 || rf) ? icell
                                      : n_oct * ttd + foff[nkey & cm]);
            }
        }
    }
    counts[0] = P.ntile;
    counts[1] = P.ni;
    counts[2] = P.any_refl ? 1 : 0;
    counts[3] = P.nmiss_father;
    return Pp;
}

// 5. every table, pads included.  tile_vsgn / slot_vbits may be null
// (no reflecting face touched); slot_ckey / slot_vbits are [ntile, nslot],
// tile_key [ntile], the rest padded as BlockMaps says.
void tile_emit(const void* plan, const int64_t* keys_l, int64_t n_l,
               int64_t ntile_pad, int64_t ni_pad, int64_t noct_pad,
               int32_t* tile_src, uint8_t* tile_ok, uint8_t* tile_vsgn,
               int64_t* slot_ckey, uint8_t* slot_vbits,
               int32_t* interp_cell, int32_t* interp_nb, int8_t* interp_sgn,
               int32_t* cell_tile, int32_t* cell_slot,
               int32_t* oct_tile, int32_t* oct_slot, int64_t* tile_key) {
    const TilePlan& P = *(const TilePlan*)plan;
    const int ndim = P.ndim, td = P.td, ttd = 1 << ndim, shift = P.shift;
    const int64_t cm = ttd - 1;
    const int64_t ncell_pad = noct_pad * ttd;
    const int32_t trash = (int32_t)(ncell_pad + ni_pad);
    int32_t foff[8];
    flat_off_table(ndim, foff);
    int es[3], k0 = 3 - ndim;
    extents(ndim, td, es);
    int64_t ostr[3];
    box_strides(ndim, P.nb, ostr);
    // per tile: the source row and refined flag of each child (Morton
    // bits) of each oct of the box, so the slot loop only gathers
    std::vector<int32_t> src8(P.nobox * ttd);
    std::vector<uint8_t> ok8(P.nobox * ttd);

    AxisTabs T(td);
    for (int64_t t = 0; t < P.ntile; t++) {
        T.fill(P, P.tile_key[t]);
        const int64_t* s = T.s.data();
        const uint8_t* r = T.r.data();
        const int32_t* row = P.row.data() + t * P.nobox;
        const uint8_t* okm = P.okmask.data() + t * P.nobox;
        const int32_t* ui = P.uidx.data() + t * P.nobox;
        for (int64_t o = 0; o < P.nobox; o++) {
            int32_t* s8 = src8.data() + o * ttd;
            uint8_t* o8 = ok8.data() + o * ttd;
            if (row[o] >= 0) {
                for (int m = 0; m < ttd; m++) {
                    s8[m] = row[o] * ttd + foff[m];
                    o8[m] = (okm[o] >> m) & 1;
                }
            } else {
                // a missed oct's requested children take consecutive rows
                int32_t next = ui[o] >= 0
                    ? (int32_t)(ncell_pad + P.ubase[ui[o]]) : trash;
                const uint8_t mk = ui[o] >= 0 ? P.umask[ui[o]] : 0;
                for (int m = 0; m < ttd; m++) {
                    s8[m] = ((mk >> m) & 1) ? next++ : trash;
                    o8[m] = 0;
                }
            }
        }
        int64_t j = t * P.nslot;
        for (int i0 = 0; i0 < es[0]; i0++)
        for (int i1 = 0; i1 < es[1]; i1++) {
            // loop levels 0 and 1 are dimensions -k0 and 1-k0 (absent
            // when negative); level 2 is the last dimension
            int64_t ck01 = 0, o01 = 0;
            uint8_t vb01 = 0;
            if (k0 <= 0) { ck01 |= s[i0]; vb01 |= r[i0];
                           o01 += (i0 >> 1) * ostr[0]; }
            if (k0 <= 1) { const int d = 1 - k0;
                           ck01 |= s[d * td + i1]; vb01 |= r[d * td + i1];
                           o01 += (i1 >> 1) * ostr[1]; }
            const int64_t* sl = s + (ndim - 1) * td;
            const uint8_t* rl = r + (ndim - 1) * td;
            for (int i2 = 0; i2 < es[2]; i2++, j++) {
                const int64_t ck = ck01 | sl[i2];
                const int64_t e = (o01 + (i2 >> 1)) * ttd + (ck & cm);
                tile_src[j] = src8[e];
                tile_ok[j] = ok8[e];
                slot_ckey[j] = ck;
                if (tile_vsgn) tile_vsgn[j] = vb01 | rl[i2];
                if (slot_vbits) slot_vbits[j] = vb01 | rl[i2];
            }
        }
    }
    for (int64_t j = P.ntile * P.nslot; j < ntile_pad * P.nslot; j++) {
        tile_src[j] = trash;
        tile_ok[j] = 0;
        if (tile_vsgn) tile_vsgn[j] = 0;
    }

    // interpolation rows: the children of each missed oct in key order
    const int64_t nu = (int64_t)P.ubase.size();
    for (int64_t u = 0; u < nu; u++) {
        int64_t rr = P.ubase[u];
        for (int m = 0; m < ttd; m++) {
            if (!((P.umask[u] >> m) & 1)) continue;
            interp_cell[rr] = P.ucell[u];
            for (int d = 0; d < ndim; d++) {
                interp_nb[(rr * ndim + d) * 2] = P.unb[(u * ndim + d) * 2];
                interp_nb[(rr * ndim + d) * 2 + 1] =
                    P.unb[(u * ndim + d) * 2 + 1];
                interp_sgn[rr * ndim + d] = (int8_t)(((m >> d) & 1) * 2 - 1);
            }
            rr++;
        }
    }
    for (int64_t rr = P.ni; rr < ni_pad; rr++) {
        interp_cell[rr] = 0;
        for (int d = 0; d < ndim; d++) {
            interp_nb[(rr * ndim + d) * 2] = 0;
            interp_nb[(rr * ndim + d) * 2 + 1] = 0;
            interp_sgn[rr * ndim + d] = 1;
        }
    }

    // scatter-back maps: tile + tile-local slot of each oct and cell
    const int64_t omask = (1ll << shift) - 1;
    const int c = P.c;
    int32_t cpow = 1;
    for (int d = 0; d < ndim; d++) cpow *= c;
    for (int64_t t = 0; t < P.ntile; t++)
        for (int64_t i = P.tile_start[t]; i < P.tile_start[t + 1]; i++) {
            int64_t a[3], oslot = 0;
            for (int d = 0; d < ndim; d++) {
                a[d] = (int64_t)compact_n((uint64_t)keys_l[i] >> d, ndim)
                    & omask;
                oslot = oslot * (1ll << shift) + a[d];
            }
            oct_tile[i] = (int32_t)t;
            oct_slot[i] = (int32_t)oslot;
            for (int f = 0; f < ttd; f++) {
                int64_t cs = 0;
                for (int d = 0; d < ndim; d++)
                    cs = cs * c + 2 * a[d] + ((f >> (ndim - 1 - d)) & 1);
                cell_tile[i * ttd + f] = (int32_t)t;
                cell_slot[i * ttd + f] = (int32_t)cs;
            }
        }
    std::copy(P.tile_key.begin(), P.tile_key.end(), tile_key);
    for (int64_t i = n_l; i < noct_pad; i++) {
        oct_tile[i] = 0;
        oct_slot[i] = 0;
        for (int f = 0; f < ttd; f++) {
            cell_tile[i * ttd + f] = 0;
            cell_slot[i * ttd + f] = cpow;
        }
    }
}

void tile_free(void* plan) { delete (TilePlan*)plan; }

}  // extern "C"
