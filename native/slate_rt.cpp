// slate_rt — native host-side runtime for slate_tpu.
//
// Reference analogue: the C++ runtime layer of the reference —
//   * include/slate/func.hh block-cyclic tile->rank lambdas and
//     include/slate/internal/MatrixStorage.hh's tile directory,
//   * src/core/Memory.cc fixed-block free-list pool (per-device tile allocator).
//
// On TPU the device compute path is XLA/Pallas, but the *host* bookkeeping —
// owner-map materialization over large tile grids, local-tile enumeration,
// redistribution planning, workspace-pool accounting — is
// exactly the kind of integer-heavy, allocation-free work the reference keeps in
// C++.  This library provides those pieces behind a plain C ABI consumed via
// ctypes (slate_tpu/native.py), with pure-Python fallbacks when the shared
// library is unavailable.
//
// Build: `make` in this directory (g++ -O3 -shared -fPIC).

#include <cstdint>
#include <mutex>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// block-cyclic maps (func.hh:100-217; GridOrder col=0 / row=1)

static inline int32_t tile_rank(int64_t i, int64_t j, int32_t p, int32_t q,
                                int32_t order) {
    return order == 0 ? static_cast<int32_t>((i % p) + (j % q) * p)
                      : static_cast<int32_t>((i % p) * q + (j % q));
}

// Fill the full mt x nt owner map (row-major out[i*nt + j]).
void srt_owner_map(int64_t mt, int64_t nt, int32_t p, int32_t q, int32_t order,
                   int32_t* out) {
    for (int64_t i = 0; i < mt; ++i) {
        int64_t ip = i % p;
        for (int64_t j = 0; j < nt; ++j) {
            int64_t jq = j % q;
            out[i * nt + j] = order == 0
                ? static_cast<int32_t>(ip + jq * p)
                : static_cast<int32_t>(ip * q + jq);
        }
    }
}

// Enumerate the tiles owned by `rank`; fills (i, j) pairs when out != nullptr.
// Returns the count either way (call once with nullptr to size the buffer).
int64_t srt_local_tiles(int64_t mt, int64_t nt, int32_t p, int32_t q,
                        int32_t order, int32_t rank, int64_t* out) {
    int64_t count = 0;
    for (int64_t i = 0; i < mt; ++i)
        for (int64_t j = 0; j < nt; ++j)
            if (tile_rank(i, j, p, q, order) == rank) {
                if (out) { out[2 * count] = i; out[2 * count + 1] = j; }
                ++count;
            }
    return count;
}

// Redistribution plan between two block-cyclic layouts (src/redistribute.cc:
// the reference walks every tile and isend/irecvs those whose owner changes).
// Fills per-tile src/dst rank maps (row-major) and returns the number of tiles
// that actually move.
int64_t srt_redist_plan(int64_t mt, int64_t nt,
                        int32_t p1, int32_t q1, int32_t order1,
                        int32_t p2, int32_t q2, int32_t order2,
                        int32_t* src, int32_t* dst) {
    int64_t moved = 0;
    for (int64_t i = 0; i < mt; ++i)
        for (int64_t j = 0; j < nt; ++j) {
            int32_t s = tile_rank(i, j, p1, q1, order1);
            int32_t d = tile_rank(i, j, p2, q2, order2);
            src[i * nt + j] = s;
            dst[i * nt + j] = d;
            if (s != d) ++moved;
        }
    return moved;
}

// ---------------------------------------------------------------------------
// fixed-block memory pool accounting (src/core/Memory.cc free list — here the
// bookkeeping layer for HBM workspace budgeting: XLA owns the actual bytes)

struct SrtPool {
    int64_t block_bytes;
    std::vector<int64_t> free_list;
    std::vector<uint8_t> in_use;   // per block-id
    int64_t peak;
    std::mutex mu;
};

void* srt_pool_new(int64_t block_bytes, int64_t nblocks) {
    auto* pool = new SrtPool();
    pool->block_bytes = block_bytes;
    pool->in_use.assign(static_cast<size_t>(nblocks), 0);
    pool->free_list.reserve(static_cast<size_t>(nblocks));
    for (int64_t b = nblocks - 1; b >= 0; --b) pool->free_list.push_back(b);
    pool->peak = 0;
    return pool;
}

void srt_pool_delete(void* p) { delete static_cast<SrtPool*>(p); }

// Returns a block id, or -1 when exhausted (Memory::alloc grows on demand in the
// reference; on TPU exhaustion must surface so the planner can spill/refit).
int64_t srt_pool_alloc(void* p) {
    auto* pool = static_cast<SrtPool*>(p);
    std::lock_guard<std::mutex> lock(pool->mu);
    if (pool->free_list.empty()) return -1;
    int64_t id = pool->free_list.back();
    pool->free_list.pop_back();
    pool->in_use[static_cast<size_t>(id)] = 1;
    int64_t used = static_cast<int64_t>(pool->in_use.size())
                 - static_cast<int64_t>(pool->free_list.size());
    if (used > pool->peak) pool->peak = used;
    return id;
}

// Returns 0 on success, -1 on double-free / bad id (Debug.cc leak checks).
int32_t srt_pool_free(void* p, int64_t id) {
    auto* pool = static_cast<SrtPool*>(p);
    std::lock_guard<std::mutex> lock(pool->mu);
    if (id < 0 || id >= static_cast<int64_t>(pool->in_use.size()) ||
        !pool->in_use[static_cast<size_t>(id)])
        return -1;
    pool->in_use[static_cast<size_t>(id)] = 0;
    pool->free_list.push_back(id);
    return 0;
}

int64_t srt_pool_in_use(void* p) {
    auto* pool = static_cast<SrtPool*>(p);
    std::lock_guard<std::mutex> lock(pool->mu);
    return static_cast<int64_t>(pool->in_use.size())
         - static_cast<int64_t>(pool->free_list.size());
}

int64_t srt_pool_capacity(void* p) {
    return static_cast<int64_t>(static_cast<SrtPool*>(p)->in_use.size());
}

int64_t srt_pool_peak(void* p) { return static_cast<SrtPool*>(p)->peak; }

}  // extern "C"
