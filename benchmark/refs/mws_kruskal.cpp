// Plain mutex watershed over one block's grid graph (Wolf et al. 2018,
// "The Mutex Watershed", Algorithm 1), the reference of
// benchmark/refs/mws_two_pass.py.  Shares nothing with the program.
//
// Edges of an outer window of uint8 affinities a(c, x), channel c pairing
// voxel x with x + offsets[c] (pairs whose partner lies outside dropped):
//
//   * channels c < n_attractive are attractive, priority a / 255 (float32);
//     an edge whose two voxels carry the same nonzero seed has priority 2,
//     above every data priority; an attractive edge of priority 0 is
//     dropped (no merge evidence);
//   * the other channels are mutex edges, priority 1 - a / 255 (float32).
//
// Edges are visited in descending priority, ties in (channel, anchor in C
// order) order: a stable counting sort, every priority being one of at
// most 2 * 256 + 1 float values.  Union-find with a mutex set per cluster:
// a mutex edge between two clusters records each in the other's set; an
// attractive edge merges its clusters unless their sets hold a mutex
// between them.  A set holds partner voxels, read through find(), and a
// merge moves the smaller set into the larger.
//
// Build: g++ -O2 -std=c++17 -shared -fPIC mws_kruskal.cpp -o <lib>.so

#include <algorithm>
#include <cstdint>
#include <map>
#include <numeric>
#include <vector>

extern "C" {

// labels[i] (i over the window's voxels in C order) = 1 + the index of
// voxel i's cluster, clusters numbered by their first voxel; returns the
// number of clusters.  ``seeds`` may be null (no voxel seeded).
int64_t mws_block(const uint8_t* affs, int64_t n_channels,
                  const int64_t* offsets, int64_t n_attractive,
                  int64_t nz, int64_t ny, int64_t nx, const int64_t* seeds,
                  int64_t* labels) {
    const int64_t n = nz * ny * nx;
    const int64_t dims[3] = {nz, ny, nx};
    // the priority of every (class, affinity) and its rank in descending
    // order, equal floats sharing a rank
    float pri[2][256];
    for (int a = 0; a < 256; ++a) {
        float x = static_cast<float>(a) / 255.0f;
        pri[0][a] = x;
        pri[1][a] = 1.0f - x;
    }
    std::map<float, int32_t, std::greater<float>> rank_of;
    rank_of[2.0f] = 0;
    for (int k = 0; k < 2; ++k)
        for (int a = 0; a < 256; ++a) rank_of[pri[k][a]] = 0;
    int32_t n_ranks = 0;
    for (auto& kv : rank_of) kv.second = n_ranks++;
    int32_t rank[2][256];
    for (int k = 0; k < 2; ++k)
        for (int a = 0; a < 256; ++a) rank[k][a] = rank_of[pri[k][a]];
    const int32_t seed_rank = rank_of[2.0f];

    // one visit of every kept edge in (channel, anchor) order
    auto for_each_edge = [&](auto&& fn) {
        for (int64_t c = 0; c < n_channels; ++c) {
            const int64_t* o = offsets + 3 * c;
            int64_t lo[3], hi[3];
            for (int d = 0; d < 3; ++d) {
                lo[d] = std::max<int64_t>(0, -o[d]);
                hi[d] = std::min<int64_t>(dims[d], dims[d] - o[d]);
            }
            const int64_t step = (o[0] * ny + o[1]) * nx + o[2];
            const uint8_t* ac = affs + c * n;
            const bool mutex = c >= n_attractive;
            for (int64_t z = lo[0]; z < hi[0]; ++z)
                for (int64_t y = lo[1]; y < hi[1]; ++y)
                    for (int64_t x = lo[2]; x < hi[2]; ++x) {
                        int64_t i = (z * ny + y) * nx + x;
                        int64_t j = i + step;
                        int32_t r;
                        if (mutex) {
                            r = rank[1][ac[i]];
                        } else if (seeds && seeds[i] != 0 &&
                                   seeds[i] == seeds[j]) {
                            r = seed_rank;
                        } else if (pri[0][ac[i]] <= 0.0f) {
                            continue;
                        } else {
                            r = rank[0][ac[i]];
                        }
                        fn(r, i, j, mutex);
                    }
        }
    };
    std::vector<int64_t> start(n_ranks + 1, 0);
    for_each_edge([&](int32_t r, int64_t, int64_t, bool) { ++start[r + 1]; });
    std::partial_sum(start.begin(), start.end(), start.begin());
    std::vector<int32_t> eu(start[n_ranks]), ev(start[n_ranks]);
    std::vector<uint8_t> em(start[n_ranks]);
    for_each_edge([&](int32_t r, int64_t i, int64_t j, bool mutex) {
        int64_t p = start[r]++;
        eu[p] = static_cast<int32_t>(i);
        ev[p] = static_cast<int32_t>(j);
        em[p] = mutex;
    });

    std::vector<int32_t> parent(n);
    std::iota(parent.begin(), parent.end(), 0);
    auto find = [&](int32_t x) {
        int32_t r = x;
        while (parent[r] != r) r = parent[r];
        while (parent[x] != r) {
            int32_t next = parent[x];
            parent[x] = r;
            x = next;
        }
        return r;
    };
    std::vector<std::vector<int32_t>> mutex_set(n);
    auto separated = [&](int32_t ra, int32_t rb) {
        if (mutex_set[ra].size() > mutex_set[rb].size()) std::swap(ra, rb);
        for (int32_t x : mutex_set[ra])
            if (find(x) == rb) return true;
        return false;
    };
    for (size_t e = 0; e < eu.size(); ++e) {
        int32_t ru = find(eu[e]), rv = find(ev[e]);
        if (ru == rv) continue;
        if (em[e]) {
            mutex_set[ru].push_back(rv);
            mutex_set[rv].push_back(ru);
            continue;
        }
        if (separated(ru, rv)) continue;
        if (mutex_set[ru].size() < mutex_set[rv].size()) std::swap(ru, rv);
        parent[rv] = ru;
        auto& big = mutex_set[ru];
        size_t before = big.size();
        big.insert(big.end(), mutex_set[rv].begin(), mutex_set[rv].end());
        std::vector<int32_t>().swap(mutex_set[rv]);
        if (big.size() >= 64 && (big.size() ^ before) > before) {
            // the size passed a power of two: drop entries that name the
            // same cluster
            for (auto& x : big) x = find(x);
            std::sort(big.begin(), big.end());
            big.erase(std::unique(big.begin(), big.end()), big.end());
        }
    }
    std::vector<int64_t> id(n, 0);
    int64_t k = 0;
    for (int64_t i = 0; i < n; ++i) {
        int32_t r = find(static_cast<int32_t>(i));
        if (id[r] == 0) id[r] = ++k;
        labels[i] = id[r];
    }
    return k;
}

}  // extern "C"
