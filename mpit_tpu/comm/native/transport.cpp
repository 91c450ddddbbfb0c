// mt_transport — native shared-memory message transport for mpit_tpu.
//
// The role the reference fills with its Lua<->MPI C binding (reference
// mpiT.c, lua-mpi.h, mpifuncs.c): a nonblocking, (rank, tag)-addressed,
// zero-copy-into-caller-buffers transport driven by poll-style Test calls,
// here for same-host role processes (the `mpirun -np N` single-host shape
// the reference is exercised in, reference README.md:28-31).  Cross-host
// paths ride XLA collectives over ICI/DCN and are not this file's job.
//
// Design (deliberately not an MPI clone):
//  * One POSIX shm segment per rank (its inbox), and in it one ring per
//    sending rank: a ring has exactly one producer and one consumer.  The
//    sender alone writes `head`, the owner alone writes `tail`, each
//    published with release and read with acquire, and there is no lock:
//    the sender copies a chunk's header and payload into the ring and then
//    publishes `head`; the owner copies a chunk out and then publishes
//    `tail`, chunk by chunk, so both copy at the same time and a sender
//    sees room as soon as one chunk has left.  Chunking bounds ring
//    residency so messages larger than the ring (the reference ships
//    640 MB parameter vectors, ptest.lua:3) stream through a small ring
//    without deadlock.  A sender that dies inside a chunk has published
//    nothing; its next incarnation takes `head` from the segment and the
//    first chunk of its next message drops what it left half-sent
//    (abandon_partials).  An owner that comes back recreates its segment,
//    and a sender stalled on the old one maps the new (kStallRemapThreshold).
//    The segment is nranks rings of ring_bytes each, but tmpfs backs only
//    the pages that were touched: a pair that never talks costs nothing.
//  * Message assembly, (rank, tag) matching, and handle state live in
//    process-local memory — the ring is purely a mailbox, so a receiver
//    polling one tag never head-of-line-blocks other tags.
//  * A receive lands where it was asked for when it was posted before
//    its message opened: the drain that reads a message's first chunk
//    binds it to the oldest receive posted for (src, tag), if that
//    receive's buffer is exactly the message's size and nothing is queued
//    for the channel ahead of it, and every chunk then goes from the ring
//    into that buffer (two copies end to end: sender into ring, ring into
//    the caller's memory).  Everything else is assembled in a buffer of
//    the transport's own and copied out by the mt_test that takes it
//    (three copies): no receive posted yet, a receive posted while the
//    message was already arriving, a buffer of another size (mt_test then
//    reports -2 and the message stays), a message queued ahead.
//    Cancelling a bound receive moves what has landed into an assembly
//    buffer, where the rest follows: the message stays whole for the next
//    receive and the cancelled buffer is not written again.  How far a
//    bound receive's buffer is filled from its front is the caller's to
//    read between calls (mt_recv_filled: the chunks land in order).
//  * Per-destination FIFO send queues give MPI-style non-overtaking order
//    between any (src, dst) pair.
//  * A send is a list of pieces, (pointer, length), placed in order: the
//    message is their bytes end to end.  mt_isend posts the list of one, a
//    whole buffer.  A send may also be posted before its bytes are whole
//    (mt_isend_pieces: a length and no piece yet), and the caller appends
//    each piece where it lies as it becomes whole (mt_send_append): no
//    byte is copied together first.  Chunks are cut across the pieces, up
//    to the last appended byte and no further, the op is done only at its
//    length, and a later send to the same rank waits behind it as behind a
//    full ring.  A piece is read until its last byte is in the ring and
//    not after (mt_send_written says how far that is).  The receiver sees
//    the header, the `total_bytes` and the bytes it always saw, one
//    message; only the instants differ.  This is the one form of a send
//    that is not yet whole.
//  * All progress happens inside mt_iprobe/mt_test/mt_isend calls from the
//    caller's cooperative scheduler, like the reference's coroutine polling
//    (reference init.lua:147-185): one thread a process reads and writes
//    the ring indices, matches messages and holds the handles.  An endpoint
//    may have helper threads beside it (mt_copy_helpers; Crew), and they do
//    one thing: a ring copy of a large chunk (circ_write, circ_read) is cut
//    into parts on cache lines, the caller and the helpers copy the parts
//    at once, and the caller goes on only when every part is copied, fork
//    and join inside the call that makes the progress.  A helper is handed
//    a destination, a source and a length and says when it is done: it
//    reads no ring index, matches no message, holds no handle and touches
//    no Python.  `head` and `tail` are published where they always were,
//    after the join, so a chunk is visible to its peer only whole: a sender
//    killed inside a chunk, be it inside a part, has still published
//    nothing, and abandon_partials, the remap after a stall,
//    mt_recv_filled's in-order mark, mt_send_written, a bound receive's
//    cancel and the assembly path see what they saw.  Without helpers (the
//    default, and what a host with no core to spare gets) every copy is one
//    memcpy on the caller's thread.  mt_ring_counts 6 and 7 say how many
//    payload bytes went in and out in parts.
//  * Where a message's time went is kept only while the endpoint's one
//    switch is on (mt_set_timing; comm/shm.py sets it from the span
//    recorder): a record a message on each end (TxTiming, RxTiming), the
//    instant a chunk was published in its header (`pub_ns`), and three
//    endpoint totals (mt_wire_ns).  A record also keeps the message's copy
//    intervals (CopyRuns; mt_op_intervals): when this end's thread was
//    inside the ring copies and how many bytes each stretch moved, one
//    interval a run of back-to-back chunks, at most kMaxRuns of them.
//    Off, no clock is read on the message path, `pub_ns` is 0, no interval
//    is kept or allocated and mt_op_timing gives nothing.  Both ends read
//    CLOCK_MONOTONIC of one host (the clock Python's time.monotonic reads
//    too), so the owner's subtraction from a sender's stamp is exact.
//
// Exported C API (ctypes bindings are generated from specs/*.json by
// gen_bindings.py, mirroring the reference's readspec.py codegen).

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <utility>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

namespace {

constexpr uint64_t kReadyMagic = 0x4d50495454505532ull;  // "MPITTPU2"
constexpr uint64_t kMaxChunk = 1ull << 22;               // 4 MB

// Start of a segment.  The ring indices follow at kIndexOffset, one per
// sending rank, and the rings' data areas from data_offset(nrings) on.
struct SegmentHeader {
  std::atomic<uint64_t> ready;  // kReadyMagic once initialized
  uint64_t nrings;              // one ring per sending rank
  uint64_t capacity;            // data bytes of each ring
};

// Each index on a cache line of its own: the two sides poll each other's.
struct RingIndex {
  alignas(64) std::atomic<uint64_t> head;  // absolute bytes written: the sender's
  alignas(64) std::atomic<uint64_t> tail;  // absolute bytes consumed: the owner's
};

constexpr uint64_t kIndexOffset = 64;

uint64_t data_offset(uint64_t nrings) {
  return (kIndexOffset + nrings * sizeof(RingIndex) + 4095) & ~4095ull;
}

struct ChunkHeader {
  int32_t src;
  int32_t tag;
  uint64_t msg_id;      // per-sender sequence, for reassembly
  uint32_t chunk_idx;
  uint32_t nchunks;
  uint64_t chunk_bytes;
  uint64_t total_bytes;
  uint64_t pub_ns;      // CLOCK_MONOTONIC at publication; 0: sender not timing
};

struct Segment {
  SegmentHeader* hdr = nullptr;
  size_t map_bytes = 0;
};

// The ring of one (sender, owner) pair inside the owner's segment.
struct Ring {
  RingIndex* idx;
  uint8_t* data;
  uint64_t capacity;
};

Ring ring_at(const Segment& seg, int src) {
  auto* base = reinterpret_cast<uint8_t*>(seg.hdr);
  uint64_t cap = seg.hdr->capacity;
  return Ring{reinterpret_cast<RingIndex*>(base + kIndexOffset) + src,
              base + data_offset(seg.hdr->nrings) + (uint64_t)src * cap, cap};
}

// A chunk and its header take at most a quarter of the ring, so the sender
// can be copying the next one in while the owner copies this one out.
uint64_t max_chunk(uint64_t capacity) {
  uint64_t fit = capacity / 4 > sizeof(ChunkHeader)
                     ? capacity / 4 - sizeof(ChunkHeader)
                     : 1;
  return kMaxChunk < fit ? kMaxChunk : fit;
}

// Message payload storage: a plain heap buffer, deliberately NOT a
// std::vector — vector's value-initialization would memset every byte
// before the ring copy overwrites it, a whole extra DRAM sweep at the
// 640 MB ptest scale.  Big buffers are recycled through Ctx::buf_cache
// so the steady-state hot path stops paying mmap+page-fault churn for
// every multi-hundred-MB message.
struct Buffer {
  std::unique_ptr<uint8_t[]> data;
  uint64_t len = 0;  // message bytes (<= cap)
  uint64_t cap = 0;  // allocation size
};

uint64_t now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

uint64_t since(uint64_t later, uint64_t earlier) {
  return later > earlier ? later - earlier : 0;
}

// When one end of a message was inside the ring copies: a run of chunks
// copied back to back in one pass of progress() is one interval (a refusal,
// an empty ring, a send short of appended bytes or the end of the pass's
// budget ends the pass for that message, and with it the run), so a shard
// of some hundred MB through a 64 MB ring is a handful of them, not one a
// chunk.  At most kMaxRuns are kept: past that the two neighbours with the
// smallest gap between them become one, gap included, and `merged` counts
// how often.  Nothing is allocated before the first timed chunk.
struct CopyRun {
  uint64_t begin = 0;
  uint64_t end = 0;
  uint64_t bytes = 0;
};

constexpr size_t kMaxRuns = 64;

struct CopyRuns {
  std::vector<CopyRun> runs;
  uint64_t pass = 0;  // the pass of progress() that copied the last chunk
  uint32_t merged = 0;

  // `bytes` copied between `begin` and `end` in pass `pass_no`; `fresh`: a
  // copy that is no chunk of that pass (the hand-over of an assembled
  // message) never continues a run.  `allocs` counts the buffers made.
  void add(uint64_t pass_no, uint64_t begin, uint64_t end, uint64_t bytes,
           bool fresh, uint64_t* allocs) {
    if (!runs.empty() && !fresh && pass == pass_no) {
      runs.back().end = end;
      runs.back().bytes += bytes;
      return;
    }
    if (runs.capacity() == 0) {
      runs.reserve(kMaxRuns);
      ++*allocs;
    }
    if (runs.size() == kMaxRuns) {
      size_t at = 0;
      for (size_t i = 1; i + 1 < runs.size(); ++i) {
        if (runs[i + 1].begin - runs[i].end <
            runs[at + 1].begin - runs[at].end) {
          at = i;
        }
      }
      runs[at].end = runs[at + 1].end;
      runs[at].bytes += runs[at + 1].bytes;
      runs.erase(runs.begin() + (ptrdiff_t)at + 1);
      merged++;
    }
    runs.push_back(CopyRun{begin, end, bytes});
    pass = fresh ? 0 : pass_no;
  }
};

// Where a sent message's time went, first attempt to place a chunk to last
// chunk published.  What is neither `copy_ns` nor `blocked_ns` of that is
// the sender's time away: the ring had room and its thread was elsewhere,
// or (`unready_ns`, a part of it) here with no appended byte of the op left.
struct TxTiming {
  uint64_t t_first = 0;
  uint64_t t_done = 0;
  uint64_t copy_ns = 0;     // inside circ_write
  uint64_t blocked_ns = 0;  // a refused placement to the next accepted one
  uint64_t t_refused = 0;   // the refusal still waited out; 0: none
  uint64_t unready_ns = 0;  // a pass that found every appended byte placed,
                            // to the next attempt with a byte to place
  uint64_t t_unready = 0;   // that pass, still waited out; 0: none
  uint64_t split_bytes = 0;  // payload bytes copied in parts (the crew)
  CopyRuns runs;            // when the thread was inside circ_write
};

// Where a received message's time went, first chunk published to message
// whole where it was asked for: `copy_ns` + `starved_ns` + `away_ns`.
struct RxTiming {
  uint64_t msg_id = 0;
  uint64_t t_first_pub = 0;  // the sender's stamp on the first chunk
  uint64_t t_first = 0;      // copy-out of the first chunk begins
  uint64_t t_done = 0;
  uint64_t last_end = 0;     // the copy before this one ended
  uint64_t copy_ns = 0;      // inside circ_read, and the hand-over memcpy
  uint64_t starved_ns = 0;   // ring empty, message partial: the sender's
  uint64_t away_ns = 0;      // a chunk lay published and was not being copied
  uint32_t chunks = 0;
  uint64_t split_bytes = 0;  // payload bytes copied in parts (the crew)
  CopyRuns runs;  // when the thread was inside circ_read or the hand-over

  // One chunk copied out between `t_start` and `t_end`.
  void chunk(const ChunkHeader& ch, uint64_t t_start, uint64_t t_end) {
    // A sender that keeps no time says nothing of when it published.
    uint64_t pub = ch.pub_ns != 0 && ch.pub_ns < t_start ? ch.pub_ns : t_start;
    if (chunks == 0) {
      msg_id = ch.msg_id;
      t_first_pub = pub;
      t_first = t_start;
      last_end = pub;
    }
    if (pub > last_end) {
      starved_ns += pub - last_end;
      last_end = pub;
    }
    away_ns += since(t_start, last_end);
    copy_ns += since(t_end, t_start);
    last_end = t_end;
    t_done = t_end;
    chunks++;
  }

  // The memcpy that hands an assembled message over, in mt_test.
  void handed_over(uint64_t t_start, uint64_t t_end) {
    away_ns += since(t_start, last_end);
    copy_ns += since(t_end, t_start);
    last_end = t_end;
    t_done = t_end;
  }
};

struct Message {
  Buffer buf;
  RxTiming rt;
};

struct Partial {
  uint64_t total = 0;
  uint64_t filled = 0;  // bytes assembled so far (chunks arrive in order)
  uint32_t seen = 0;
  int32_t tag = 0;
  int64_t bound = 0;  // receive whose buffer the chunks land in; 0: buf
  Buffer buf;
  RxTiming rt;
};

// A run of a send's bytes, where the caller keeps them.
struct Piece {
  const uint8_t* data = nullptr;
  uint64_t len = 0;
};

struct SendOp {
  int dst = -1;
  int tag = 0;
  std::deque<Piece> pieces;  // appended and not yet wholly placed, in order
  uint64_t piece_off = 0;    // bytes of the front piece already placed
  uint64_t len = 0;
  uint64_t appended = 0;  // bytes of all the pieces appended so far (<= len)
  uint64_t written = 0;   // payload bytes already placed in the ring
  uint64_t early_bytes = 0;  // those placed while `appended` was short of len
  uint64_t msg_id = 0;
  uint32_t nchunks = 0;
  uint32_t next_chunk = 0;
  bool done = false;
  bool cancelled = false;
  uint32_t stalls = 0;  // consecutive pump passes that found the ring full
  TxTiming tt;
};

// After this many consecutive passes that placed nothing in a full peer ring,
// suspect a stale mapping (peer crashed and recreated its segment) and
// remap.  Normal backpressure resets the counter on any progress.
constexpr uint32_t kStallRemapThreshold = 4096;

struct RecvOp {
  int src = -1;
  int tag = 0;
  uint8_t* out = nullptr;
  uint64_t cap = 0;
  uint64_t size = 0;
  uint64_t msg_id = 0;  // the sender's message landing in `out`, once bound
  bool bound = false;
  bool torn = false;  // a message had begun to land in `out` and was abandoned
  bool done = false;
  bool cancelled = false;
  bool size_mismatch = false;
  RxTiming rt;
};

struct Crew;  // the endpoint's helper threads, below

struct Ctx {
  std::string ns;
  int rank = -1;
  int nranks = 0;
  uint64_t ring_bytes = 0;
  Segment own;
  std::vector<Segment> peers;  // lazily opened inboxes of other ranks
  std::map<std::pair<int, int>, std::deque<Message>> ready;      // (src,tag)
  std::map<std::pair<int, uint64_t>, Partial> partial;           // (src,msg_id)
  std::map<int64_t, SendOp> sends;
  std::map<int64_t, RecvOp> recvs;
  std::map<int, std::deque<int64_t>> send_q;  // per-destination FIFO
  std::vector<Buffer> buf_cache;  // recycled big message buffers
  int64_t next_handle = 1;
  uint64_t next_msg_id = 1;
  // Bytes of the messages that became whole in a caller's buffer, and in
  // an assembly buffer (mt_rx_bytes).
  uint64_t rx_direct_bytes = 0;
  uint64_t rx_assembled_bytes = 0;
  // Chunks placed in a peer's ring, placements refused by a full ring (the
  // sender waited for the owner), chunks copied out of an own ring, and
  // those of them during whose copy the ring's head moved (the sender was
  // copying into the ring at the same time), and payload bytes placed while
  // their op's pieces were short of its length (mt_ring_counts).
  uint64_t tx_chunks = 0;
  uint64_t tx_ring_full = 0;
  uint64_t rx_chunks = 0;
  uint64_t rx_overlap_chunks = 0;
  uint64_t tx_early_bytes = 0;
  // Payload bytes that went into a peer's ring, and out of an own ring, in
  // parts copied at once (mt_ring_counts 6, 7); `crew` copies them with the
  // caller, and without one (the default) every copy is one memcpy here.
  uint64_t tx_split_bytes = 0;
  uint64_t rx_split_bytes = 0;
  Crew* crew = nullptr;
  // While `timing` (mt_set_timing): ns inside circ_write, inside circ_read
  // and the hand-over memcpy, and inside progress() with that memcpy
  // (mt_wire_ns).  Less the two copies the last is the cost of polling.
  bool timing = false;
  uint64_t tx_copy_ns = 0;
  uint64_t rx_copy_ns = 0;
  uint64_t progress_ns = 0;
  // While `timing`: the passes of progress() so far (a message's copy runs
  // are cut by it) and the interval buffers allocated (mt_ring_counts 5).
  uint64_t pass_no = 0;
  uint64_t run_buffers = 0;
  std::string last_error;
};

// Only buffers this big are worth recycling (below it, allocator churn is
// cheap and caching would let one huge cached buffer serve tiny acks).
constexpr uint64_t kBufCacheMin = 1ull << 20;
constexpr size_t kBufCacheSlots = 8;

Buffer alloc_buffer(Ctx* ctx, uint64_t n) {
  Buffer buf;
  if (n >= kBufCacheMin) {
    size_t best = SIZE_MAX;
    for (size_t i = 0; i < ctx->buf_cache.size(); ++i) {
      uint64_t cap = ctx->buf_cache[i].cap;
      if (cap >= n && (best == SIZE_MAX || cap < ctx->buf_cache[best].cap)) {
        best = i;
      }
    }
    if (best != SIZE_MAX) {
      buf = std::move(ctx->buf_cache[best]);
      ctx->buf_cache.erase(ctx->buf_cache.begin() + (ptrdiff_t)best);
      buf.len = n;
      return buf;
    }
  }
  buf.data.reset(n > 0 ? new uint8_t[n] : nullptr);  // uninitialized
  buf.cap = n;
  buf.len = n;
  return buf;
}

void recycle_buffer(Ctx* ctx, Buffer&& buf) {
  if (buf.cap >= kBufCacheMin && ctx->buf_cache.size() < kBufCacheSlots) {
    ctx->buf_cache.push_back(std::move(buf));
  }
}

std::string shm_name(const std::string& ns, int rank) {
  return "/mt_" + ns + "_r" + std::to_string(rank);
}

bool map_segment(const std::string& name, uint64_t nrings, uint64_t ring_bytes,
                 bool create, Segment* out, std::string* err) {
  int flags = create ? (O_CREAT | O_RDWR) : O_RDWR;
  int fd = shm_open(name.c_str(), flags, 0600);
  if (fd < 0) {
    if (err) *err = "shm_open " + name + ": " + std::strerror(errno);
    return false;
  }
  // A new file is a hole from end to end: every index reads zero.
  size_t total = data_offset(nrings) + nrings * ring_bytes;
  if (create && ftruncate(fd, (off_t)total) != 0) {
    if (err) *err = "ftruncate " + name + ": " + std::strerror(errno);
    close(fd);
    return false;
  }
  if (!create) {
    // The creator sizes the segment; wait for a nonzero size.
    struct stat st;
    if (fstat(fd, &st) != 0 || (size_t)st.st_size < data_offset(nrings)) {
      close(fd);
      if (err) *err = "peer segment not sized yet";
      return false;
    }
    total = (size_t)st.st_size;
  }
  void* mem = mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) {
    if (err) *err = "mmap " + name + ": " + std::strerror(errno);
    return false;
  }
  out->hdr = reinterpret_cast<SegmentHeader*>(mem);
  out->map_bytes = total;
  return true;
}

// -- the copy crew ------------------------------------------------------------
//
// The helper threads of one endpoint (mt_copy_helpers; none by default).  A
// ring copy of at least `min_bytes` is cut into parts on the destination's
// cache lines, twice as many as there are threads to copy them, and the
// caller and the helpers take part after part off `open` until none is
// left; the caller then waits for the parts the helpers took and returns:
// fork and join inside the one memcpy's place, so whoever called sees a
// copy that is whole, as before.  A helper knows nothing but the job: it
// reads no ring index and no message, and it is told a destination, a
// source and a length.  The job's fields are plain: they are written before
// `open` is stored, read only by whoever took a part off `open`, and not
// written again before every part taken has been counted in `copied`.  A
// helper that is late (asleep, or off its core) finds `open` at 0 and the
// caller has copied its share: nobody waits for a thread that has not
// begun.  A helper spins for `spin_ns` after the last part it saw (the next
// chunk of a large message follows within microseconds, and a futex wake
// costs a good part of a chunk's half) and then sleeps on the condition
// variable: it costs a core only while large messages move.  While `timed`
// (mt_set_timing) a helper adds up, by its own readings of the clock, the ns
// it spent inside its parts' copies and the ns it spun with no part to take
// (mt_wire_ns 3, 4): together they are its time on a core.
struct Crew {
  std::vector<std::thread> threads;
  uint64_t min_bytes = 0;
  uint64_t spin_ns = 0;
  uint8_t* dst = nullptr;
  const uint8_t* src = nullptr;
  uint64_t len = 0;
  uint64_t lead = 0;  // bytes of `dst` before its first whole cache line
  uint64_t part = 0;  // bytes a part, whole cache lines
  alignas(64) std::atomic<uint32_t> open{0};    // parts nobody has taken yet
  alignas(64) std::atomic<uint32_t> copied{0};  // parts the helpers finished
  alignas(64) std::atomic<uint32_t> asleep{0};
  std::atomic<bool> closing{false};
  std::atomic<bool> timed{false};
  std::atomic<uint64_t> copy_ns{0};
  std::atomic<uint64_t> spun_ns{0};
  std::mutex mu;
  std::condition_variable cv;
};

void cpu_relax() {
#if defined(__x86_64__) || defined(__i386__)
  __builtin_ia32_pause();
#elif defined(__aarch64__)
  asm volatile("yield");
#endif
}

// Part `idx` of the open job: [lead + idx * part, lead + (idx + 1) * part),
// the first from 0 and the last to the end.
void crew_copy_part(const Crew* crew, uint32_t idx) {
  uint64_t lo = idx == 0 ? 0 : crew->lead + idx * crew->part;
  uint64_t hi = std::min(crew->len, crew->lead + (idx + 1) * crew->part);
  std::memcpy(crew->dst + lo, crew->src + lo, hi - lo);
}

// Take one part off `open`; false when none is left.
bool crew_take(Crew* crew, uint32_t* idx) {
  uint32_t left = crew->open.load(std::memory_order_acquire);
  while (left > 0) {
    if (crew->open.compare_exchange_weak(left, left - 1,
                                         std::memory_order_acq_rel)) {
      *idx = left - 1;
      return true;
    }
  }
  return false;
}

void crew_helper(Crew* crew) {
#if defined(__linux__)
  // Once a thread, before any job: the name a rank's census of its threads
  // counts a helper under (obs/profile.py thread_census).
  pthread_setname_np(pthread_self(), "mpit-crew");
#endif
  uint64_t idle_since = 0;  // first look at the clock since the last part
  for (uint32_t spins = 0;; ++spins) {
    uint32_t idx;
    if (crew_take(crew, &idx)) {
      const uint64_t t_part =
          crew->timed.load(std::memory_order_relaxed) ? now_ns() : 0;
      crew_copy_part(crew, idx);
      crew->copied.fetch_add(1, std::memory_order_release);
      if (t_part != 0) {
        crew->copy_ns.fetch_add(now_ns() - t_part, std::memory_order_relaxed);
        if (idle_since != 0) {
          crew->spun_ns.fetch_add(t_part - idle_since,
                                  std::memory_order_relaxed);
        }
      }
      idle_since = 0;
      continue;
    }
    if (crew->closing.load(std::memory_order_acquire)) return;
    cpu_relax();
    if (spins % 128 != 0) continue;
    const uint64_t now = now_ns();
    if (idle_since == 0) idle_since = now;
    if (now - idle_since < crew->spin_ns) continue;
    if (crew->timed.load(std::memory_order_relaxed)) {
      crew->spun_ns.fetch_add(now - idle_since, std::memory_order_relaxed);
    }
    std::unique_lock<std::mutex> lk(crew->mu);
    crew->asleep.fetch_add(1, std::memory_order_seq_cst);
    crew->cv.wait(lk, [crew] {
      return crew->open.load(std::memory_order_seq_cst) > 0 ||
             crew->closing.load(std::memory_order_acquire);
    });
    crew->asleep.fetch_sub(1, std::memory_order_seq_cst);
    idle_since = 0;
  }
}

// Copy `n` bytes; those of them that were copied in parts (all or none).
uint64_t copy_bytes(Crew* crew, void* dst, const void* src, uint64_t n) {
  if (crew == nullptr || n < crew->min_bytes) {
    std::memcpy(dst, src, n);
    return 0;
  }
  const uint64_t threads = crew->threads.size() + 1;
  crew->dst = static_cast<uint8_t*>(dst);
  crew->src = static_cast<const uint8_t*>(src);
  crew->len = n;
  crew->lead = (64 - reinterpret_cast<uintptr_t>(dst) % 64) % 64;
  crew->part = (n / (2 * threads) + 63) & ~63ull;
  const uint32_t nparts =
      (uint32_t)((n - crew->lead + crew->part - 1) / crew->part);
  crew->copied.store(0, std::memory_order_relaxed);
  crew->open.store(nparts, std::memory_order_seq_cst);
  if (crew->asleep.load(std::memory_order_seq_cst) > 0) {
    { std::lock_guard<std::mutex> lk(crew->mu); }  // it is waiting, or awake
    crew->cv.notify_all();
  }
  uint32_t mine = 0;
  for (uint32_t idx; crew_take(crew, &idx); ++mine) crew_copy_part(crew, idx);
  while (crew->copied.load(std::memory_order_acquire) + mine < nparts) {
    cpu_relax();
  }
  return n;
}

void crew_close(Crew* crew) {
  if (crew == nullptr) return;
  {
    std::lock_guard<std::mutex> lk(crew->mu);
    crew->closing.store(true, std::memory_order_release);
  }
  crew->cv.notify_all();
  for (auto& t : crew->threads) t.join();
  delete crew;
}

// Both return the bytes that were copied in parts.
uint64_t circ_write(Crew* crew, const Ring& ring, uint64_t pos,
                    const void* src, uint64_t n) {
  uint64_t off = pos % ring.capacity;
  uint64_t first = (off + n <= ring.capacity) ? n : ring.capacity - off;
  uint64_t split = copy_bytes(crew, ring.data + off, src, first);
  if (first < n) {
    split += copy_bytes(crew, ring.data,
                        reinterpret_cast<const uint8_t*>(src) + first,
                        n - first);
  }
  return split;
}

uint64_t circ_read(Crew* crew, const Ring& ring, uint64_t pos, void* dst,
                   uint64_t n) {
  uint64_t off = pos % ring.capacity;
  uint64_t first = (off + n <= ring.capacity) ? n : ring.capacity - off;
  uint64_t split = copy_bytes(crew, dst, ring.data + off, first);
  if (first < n) {
    split += copy_bytes(crew, reinterpret_cast<uint8_t*>(dst) + first,
                        ring.data, n - first);
  }
  return split;
}

void unmap_peer(Ctx* ctx, int dst) {
  Segment& seg = ctx->peers[dst];
  if (seg.hdr != nullptr) {
    munmap(seg.hdr, seg.map_bytes);
    seg = Segment{};
  }
}

// The segment of rank `dst`, once its owner has made it ready for a gang
// of this shape; nullptr until then (the caller retries on next progress).
Segment* peer_segment(Ctx* ctx, int dst) {
  if (dst < 0 || dst >= ctx->nranks) return nullptr;
  Segment& seg = ctx->peers[dst];
  if (seg.hdr == nullptr &&
      !map_segment(shm_name(ctx->ns, dst), (uint64_t)ctx->nranks,
                   ctx->ring_bytes, /*create=*/false, &seg, nullptr)) {
    return nullptr;  // peer not up yet
  }
  if (seg.hdr->ready.load(std::memory_order_acquire) != kReadyMagic) {
    return nullptr;
  }
  if (seg.hdr->nrings != (uint64_t)ctx->nranks ||
      seg.map_bytes <
          data_offset(seg.hdr->nrings) + seg.hdr->nrings * seg.hdr->capacity) {
    unmap_peer(ctx, dst);  // a gang of another shape left it behind
    return nullptr;
  }
  return &seg;
}

// The receive a message of `total` bytes from (src, tag) that opens now
// lands in: the oldest one posted and not yet matched, if its buffer is
// exactly that size and no whole message waits on the channel ahead of it
// (a half-assembled one cannot: a sender's messages arrive one at a time).
// Handles count up, so the map's order is the order of posting.
RecvOp* posted_recv(Ctx* ctx, int src, int tag, uint64_t total,
                    int64_t* handle) {
  auto box = ctx->ready.find({src, tag});
  if (box != ctx->ready.end() && !box->second.empty()) return nullptr;
  for (auto& [h, op] : ctx->recvs) {
    if (op.src != src || op.tag != tag || op.bound || op.done) continue;
    if (op.cap != total) return nullptr;
    *handle = h;
    return &op;
  }
  return nullptr;
}

// A sender places one message at a time, so the first chunk of a new one
// says that whatever it left unfinished (a send cancelled part-way, a
// peer that died and came back) will never be finished: a receive bound
// to it goes back to waiting, an assembly buffer is recycled.
void abandon_partials(Ctx* ctx, int src) {
  auto it = ctx->partial.lower_bound({src, 0});
  while (it != ctx->partial.end() && it->first.first == src) {
    Partial& part = it->second;
    if (part.bound != 0) {
      RecvOp& op = ctx->recvs.at(part.bound);
      op.bound = false;
      op.msg_id = 0;
      op.torn = op.torn || part.filled > 0;
    } else {
      recycle_buffer(ctx, std::move(part.buf));
    }
    it = ctx->partial.erase(it);
  }
}

// Drain one ring of the own inbox, as far as its head stood when the pass
// began (a sender copying in beside the drain could otherwise hold the
// thread here for a whole message).  Payload bytes go from the ring into
// the buffer of the receive the message is bound to, or else into its
// assembly buffer — one copy either way, into uninitialized storage, with
// no lock held; an assembled message pays a second one when mt_test hands
// it over.  `tail` is published after every chunk.  While timing, a chunk's
// copy-out is stamped at both ends and booked on its message's record,
// which ends up on the receive (bound, or in mt_test) that takes it.
void drain_ring(Ctx* ctx, const Ring& ring) {
  const bool timing = ctx->timing;
  uint64_t tail = ring.idx->tail.load(std::memory_order_relaxed);
  const uint64_t limit = ring.idx->head.load(std::memory_order_acquire);
  while (tail < limit) {
    const uint64_t head_before = ring.idx->head.load(std::memory_order_acquire);
    const uint64_t t_start = timing ? now_ns() : 0;
    RxTiming* rt = nullptr;  // the record of this chunk's message
    RxTiming whole;          // ... of a message that is one chunk
    ChunkHeader ch;
    circ_read(ctx->crew, ring, tail, &ch, sizeof(ch));
    tail += sizeof(ch);
    uint64_t split = 0;  // bytes of this chunk copied in parts
    // A first chunk opens a message: it lands in the receive posted for
    // it, if there is one, and in an assembly buffer otherwise.
    RecvOp* op = nullptr;
    int64_t handle = 0;
    if (ch.chunk_idx == 0) {
      abandon_partials(ctx, ch.src);
      op = posted_recv(ctx, ch.src, ch.tag, ch.total_bytes, &handle);
    }
    RxTiming* landed = nullptr;  // where a message whole now keeps its record
    if (ch.chunk_bytes == ch.total_bytes) {  // complete in one chunk
      rt = &whole;
      if (op != nullptr) {
        if (ch.chunk_bytes > 0) {
          split = circ_read(ctx->crew, ring, tail, op->out, ch.chunk_bytes);
        }
        op->size = ch.total_bytes;
        op->bound = true;
        op->done = true;
        landed = &op->rt;
        ctx->rx_direct_bytes += ch.total_bytes;
      } else {
        Buffer buf = alloc_buffer(ctx, ch.total_bytes);
        if (ch.chunk_bytes > 0) {
          split = circ_read(ctx->crew, ring, tail, buf.data.get(),
                            ch.chunk_bytes);
        }
        auto& box = ctx->ready[{ch.src, ch.tag}];
        box.push_back(Message{std::move(buf), RxTiming{}});
        landed = &box.back().rt;
        ctx->rx_assembled_bytes += ch.total_bytes;
      }
    } else {
      auto key = std::make_pair(ch.src, ch.msg_id);
      Partial& part = ctx->partial[key];
      if (part.seen == 0) {
        part.total = ch.total_bytes;
        part.tag = ch.tag;
        if (op != nullptr) {
          op->bound = true;
          op->msg_id = ch.msg_id;
          part.bound = handle;
        } else {
          part.buf = alloc_buffer(ctx, ch.total_bytes);
        }
      } else if (part.bound != 0) {
        op = &ctx->recvs.at(part.bound);
      }
      uint8_t* dst = op != nullptr ? op->out : part.buf.data.get();
      uint64_t n = ch.chunk_bytes;  // clamp defensively; completion is byte-based
      if (part.filled + n > part.total) n = part.total - part.filled;
      if (n > 0) split = circ_read(ctx->crew, ring, tail, dst + part.filled, n);
      part.filled += ch.chunk_bytes;
      part.seen++;
      rt = &part.rt;
      if (part.filled >= part.total) {
        if (op != nullptr) {
          op->size = part.total;
          op->done = true;
          landed = &op->rt;
          ctx->rx_direct_bytes += part.total;
        } else {
          auto& box = ctx->ready[{ch.src, part.tag}];
          box.push_back(Message{std::move(part.buf), RxTiming{}});
          landed = &box.back().rt;
          ctx->rx_assembled_bytes += part.total;
        }
      }
    }
    tail += ch.chunk_bytes;
    ring.idx->tail.store(tail, std::memory_order_release);
    ctx->rx_chunks++;
    ctx->rx_split_bytes += split;
    const bool overlapped =
        ring.idx->head.load(std::memory_order_acquire) != head_before;
    ctx->rx_overlap_chunks += overlapped;
    if (timing) {
      const uint64_t t_end = now_ns();
      ctx->rx_copy_ns += t_end - t_start;
      rt->chunk(ch, t_start, t_end);
      rt->split_bytes += split;
      rt->runs.add(ctx->pass_no, t_start, t_end, ch.chunk_bytes,
                   /*fresh=*/false, &ctx->run_buffers);
    }
    if (landed != nullptr) {
      if (timing) *landed = std::move(*rt);
      if (rt != &whole) ctx->partial.erase({ch.src, ch.msg_id});
    }
  }
}

void drain_inbox(Ctx* ctx) {
  for (int src = 0; src < ctx->nranks; ++src) {
    drain_ring(ctx, ring_at(ctx->own, src));
  }
}

// Take a receive off the books.  One that a message is bound to hands the
// message back first: what has landed in its buffer is copied into an
// assembly buffer, where the rest of the chunks follow (or, if the message
// is whole and was never collected, to the head of its channel's queue),
// so the next receive finds the message whole and nothing writes to this
// one's buffer again.
void drop_recv(Ctx* ctx, std::map<int64_t, RecvOp>::iterator it) {
  RecvOp& op = it->second;
  if (op.bound) {
    Buffer buf = alloc_buffer(ctx, op.cap);
    if (op.done) {
      if (op.cap > 0) std::memcpy(buf.data.get(), op.out, op.cap);
      ctx->ready[{op.src, op.tag}].push_front(Message{std::move(buf), op.rt});
    } else {
      Partial& part = ctx->partial.at({op.src, op.msg_id});
      if (part.filled > 0) std::memcpy(buf.data.get(), op.out, part.filled);
      part.buf = std::move(buf);
      part.bound = 0;
    }
  }
  ctx->recvs.erase(it);
}

// Place more chunks of the front send ops of each destination, at most one
// ring's worth of bytes a destination and pass: with the owner draining
// beside it the ring may never fill, and the caller's thread has its other
// destinations, its inbox and its deadlines to look at.  A chunk is cut
// across the op's pieces in order, ends at the last appended byte at the
// latest, and is cut short of a whole one only where nothing more is
// appended; an op at its last appended byte and short of its length stays
// at the front of its queue, like one before a full ring.  While
// timing, the payload goes in first and the header, stamped with the
// instant, after it; both lie in the ring before `head` says so either way.
void pump_sends(Ctx* ctx) {
  const bool timing = ctx->timing;
  for (auto& [dst, queue] : ctx->send_q) {
    uint64_t budget = UINT64_MAX;  // the ring's capacity, once it is mapped
    while (!queue.empty()) {
      int64_t handle = queue.front();
      auto it = ctx->sends.find(handle);
      if (it == ctx->sends.end() || it->second.cancelled || it->second.done) {
        queue.pop_front();
        continue;
      }
      SendOp& op = it->second;
      Segment* seg = peer_segment(ctx, dst);
      if (seg == nullptr) break;  // destination not up yet
      const Ring ring = ring_at(*seg, ctx->rank);
      if (budget > ring.capacity) budget = ring.capacity;
      const uint64_t chunk_max = max_chunk(ring.capacity);
      uint64_t head = ring.idx->head.load(std::memory_order_relaxed);
      bool full = false;
      while (!op.done) {
        uint64_t remaining = op.appended - op.written;
        uint64_t chunk = remaining < chunk_max ? remaining : chunk_max;
        uint64_t need = sizeof(ChunkHeader) + chunk;
        if (need > budget) break;
        const uint64_t t_try = timing ? now_ns() : 0;
        if (timing && op.tt.t_first == 0) op.tt.t_first = t_try;
        if (chunk == 0 && op.len > 0) {  // nothing appended is left: the caller's
          if (timing && op.tt.t_unready == 0) op.tt.t_unready = t_try;
          break;
        }
        if (timing && op.tt.t_unready != 0) {
          op.tt.unready_ns += t_try - op.tt.t_unready;
          op.tt.t_unready = 0;
        }
        uint64_t used = head - ring.idx->tail.load(std::memory_order_acquire);
        if (ring.capacity - used < need) {
          ctx->tx_ring_full++;
          full = true;
          if (timing && op.tt.t_refused == 0) op.tt.t_refused = t_try;
          break;
        }
        ChunkHeader ch;
        ch.src = ctx->rank;
        ch.tag = op.tag;
        ch.msg_id = op.msg_id;
        ch.chunk_idx = op.next_chunk;
        ch.nchunks = 0;  // informational; completion is byte-based
        ch.chunk_bytes = chunk;
        ch.total_bytes = op.len;
        ch.pub_ns = 0;
        uint64_t split = 0;  // bytes of this chunk copied in parts
        for (uint64_t placed = 0; placed < chunk;) {
          Piece& piece = op.pieces.front();
          uint64_t n = piece.len - op.piece_off;
          if (n > chunk - placed) n = chunk - placed;
          split += circ_write(ctx->crew, ring, head + sizeof(ch) + placed,
                              piece.data + op.piece_off, n);
          placed += n;
          op.piece_off += n;
          if (op.piece_off == piece.len) {  // its last byte is in the ring
            op.pieces.pop_front();
            op.piece_off = 0;
          }
        }
        if (timing) {
          const uint64_t t_pub = now_ns();
          ch.pub_ns = t_pub;
          if (op.tt.t_refused != 0) {
            op.tt.blocked_ns += t_try - op.tt.t_refused;
            op.tt.t_refused = 0;
          }
          op.tt.copy_ns += t_pub - t_try;
          op.tt.t_done = t_pub;
          op.tt.split_bytes += split;
          op.tt.runs.add(ctx->pass_no, t_try, t_pub, chunk, /*fresh=*/false,
                         &ctx->run_buffers);
          ctx->tx_copy_ns += t_pub - t_try;
        }
        circ_write(ctx->crew, ring, head, &ch, sizeof(ch));
        head += need;
        ring.idx->head.store(head, std::memory_order_release);
        budget -= need;
        ctx->tx_chunks++;
        ctx->tx_split_bytes += split;
        if (op.appended < op.len) {
          op.early_bytes += chunk;
          ctx->tx_early_bytes += chunk;
        }
        op.written += chunk;
        op.next_chunk++;
        op.stalls = 0;
        if (op.written >= op.len) op.done = true;
      }
      if (!op.done) {
        // A full ring pass after pass: past the threshold assume a stale
        // mapping (the peer recreated its segment) and remap.
        if (full && ++op.stalls >= kStallRemapThreshold) {
          op.stalls = 0;
          unmap_peer(ctx, dst);
        }
        break;  // keep FIFO order, stop for this dst
      }
      queue.pop_front();
    }
  }
}

void progress(Ctx* ctx) {
  const uint64_t t_in = ctx->timing ? now_ns() : 0;
  if (ctx->timing) ctx->pass_no++;
  drain_inbox(ctx);
  pump_sends(ctx);
  if (ctx->timing) ctx->progress_ns += now_ns() - t_in;
}

}  // namespace

extern "C" {

void* mt_init(const char* ns, int rank, int nranks, uint64_t ring_bytes) {
  auto* ctx = new Ctx();
  ctx->ns = ns;
  ctx->rank = rank;
  ctx->nranks = nranks;
  ctx->ring_bytes = ring_bytes;
  ctx->peers.resize(nranks);
  std::string name = shm_name(ctx->ns, rank);
  shm_unlink(name.c_str());  // clear any stale segment from a crashed run
  std::string err;
  if (!map_segment(name, (uint64_t)nranks, ring_bytes, /*create=*/true,
                   &ctx->own, &err)) {
    std::fprintf(stderr, "mt_init: %s\n", err.c_str());
    delete ctx;
    return nullptr;
  }
  ctx->own.hdr->nrings = (uint64_t)nranks;
  ctx->own.hdr->capacity = ring_bytes;
  ctx->own.hdr->ready.store(kReadyMagic, std::memory_order_release);
  return ctx;
}

// Give the endpoint `n` helper threads that copy a ring copy of at least
// `min_bytes` in parts with the caller (Crew); they wait spinning for
// `spin_ns` after a part and asleep from then on.  Once, before the first
// message; n <= 0 changes nothing.  Returns the helpers started.
int32_t mt_copy_helpers(void* vctx, int32_t n, uint64_t min_bytes,
                        uint64_t spin_ns) {
  auto* ctx = static_cast<Ctx*>(vctx);
  if (n <= 0 || ctx->crew != nullptr) return 0;
  auto* crew = new Crew();
  crew->min_bytes = std::max<uint64_t>(min_bytes, 4096);
  crew->spin_ns = spin_ns;
  crew->timed.store(ctx->timing, std::memory_order_relaxed);
  crew->threads.reserve((size_t)n);
  for (int32_t i = 0; i < n; ++i) crew->threads.emplace_back(crew_helper, crew);
  ctx->crew = crew;
  return n;
}

void mt_finalize(void* vctx) {
  auto* ctx = static_cast<Ctx*>(vctx);
  if (ctx == nullptr) return;
  crew_close(ctx->crew);
  if (ctx->own.hdr != nullptr) {
    munmap(ctx->own.hdr, ctx->own.map_bytes);
    shm_unlink(shm_name(ctx->ns, ctx->rank).c_str());
  }
  for (int dst = 0; dst < ctx->nranks; ++dst) unmap_peer(ctx, dst);
  delete ctx;
}

int mt_rank(void* vctx) { return static_cast<Ctx*>(vctx)->rank; }
int mt_nranks(void* vctx) { return static_cast<Ctx*>(vctx)->nranks; }

// A send of `len` bytes of which no piece has been appended yet
// (mt_send_append): the op takes its place in the destination's queue and
// its message id now, and its bytes leave as they are appended.
int64_t mt_isend_pieces(void* vctx, int dst, int tag, uint64_t len) {
  auto* ctx = static_cast<Ctx*>(vctx);
  if (dst < 0 || dst >= ctx->nranks) return -1;
  SendOp op;
  op.dst = dst;
  op.tag = tag;
  op.len = len;
  op.msg_id = ctx->next_msg_id++;
  int64_t handle = ctx->next_handle++;
  ctx->sends[handle] = op;
  ctx->send_q[dst].push_back(handle);
  return handle;
}

// Append the next `n` bytes of a pending send, which lie at `data` and stay
// there unchanged until they are in the ring (mt_send_written).  Returns the
// bytes appended so far after the call; -1 for a handle that is no pending
// send (unknown, cancelled, or done and forgotten); -2, and nothing is
// appended, if the pieces would pass the send's length.  The next call that
// makes progress places what was appended.
int64_t mt_send_append(void* vctx, int64_t handle, const void* data,
                       uint64_t n) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto sit = ctx->sends.find(handle);
  if (sit == ctx->sends.end() || sit->second.cancelled) return -1;
  SendOp& op = sit->second;
  if (n > op.len - op.appended) return -2;
  if (n > 0) {
    op.pieces.push_back(Piece{static_cast<const uint8_t*>(data), n});
    op.appended += n;
  }
  return (int64_t)op.appended;
}

// The whole send: the list of one piece.
int64_t mt_isend(void* vctx, int dst, int tag, const void* data, uint64_t len) {
  int64_t handle = mt_isend_pieces(vctx, dst, tag, len);
  if (handle < 0) return handle;
  mt_send_append(vctx, handle, data, len);
  progress(static_cast<Ctx*>(vctx));
  return handle;
}

// Payload bytes of a pending send that are in the ring: every piece that
// ends at or before that byte has been read for the last time.  -1 for a
// handle that is no pending send; a finished one whose record is kept
// (timing) says its length.
int64_t mt_send_written(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto sit = ctx->sends.find(handle);
  if (sit == ctx->sends.end() || sit->second.cancelled) return -1;
  return (int64_t)sit->second.written;
}

int64_t mt_irecv(void* vctx, int src, int tag, void* out, uint64_t cap) {
  auto* ctx = static_cast<Ctx*>(vctx);
  if (src < 0 || src >= ctx->nranks) return -1;
  RecvOp op;
  op.src = src;
  op.tag = tag;
  op.out = static_cast<uint8_t*>(out);
  op.cap = cap;
  int64_t handle = ctx->next_handle++;
  ctx->recvs[handle] = op;
  return handle;
}

int mt_iprobe(void* vctx, int src, int tag) {
  auto* ctx = static_cast<Ctx*>(vctx);
  progress(ctx);
  auto it = ctx->ready.find({src, tag});
  return (it != ctx->ready.end() && !it->second.empty()) ? 1 : 0;
}

int64_t mt_probe_size(void* vctx, int src, int tag) {
  auto* ctx = static_cast<Ctx*>(vctx);
  progress(ctx);
  auto it = ctx->ready.find({src, tag});
  if (it == ctx->ready.end() || it->second.empty()) return -1;
  return (int64_t)it->second.front().buf.len;
}

// Returns 1 complete, 0 pending, -1 unknown handle, -2 size mismatch.
int mt_test(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  progress(ctx);
  auto sit = ctx->sends.find(handle);
  if (sit != ctx->sends.end()) {
    if (sit->second.cancelled) return -1;
    if (sit->second.done) {
      // While timing the op keeps its record for mt_op_timing until
      // mt_release forgets it.
      if (!ctx->timing) ctx->sends.erase(sit);
      return 1;
    }
    return 0;
  }
  auto rit = ctx->recvs.find(handle);
  if (rit != ctx->recvs.end()) {
    RecvOp& op = rit->second;
    if (op.cancelled) return -1;
    if (op.done) return 1;
    if (op.bound) return 0;  // its message is landing in op.out
    auto box = ctx->ready.find({op.src, op.tag});
    if (box == ctx->ready.end() || box->second.empty()) return 0;
    Message& msg = box->second.front();
    if (msg.buf.len != op.cap) {
      op.size_mismatch = true;
      op.size = msg.buf.len;
      return -2;
    }
    const uint64_t t_copy = ctx->timing ? now_ns() : 0;
    if (op.cap > 0) std::memcpy(op.out, msg.buf.data.get(), op.cap);
    if (ctx->timing) {
      const uint64_t t_end = now_ns();
      op.rt = std::move(msg.rt);
      op.rt.handed_over(t_copy, t_end);
      op.rt.runs.add(ctx->pass_no, t_copy, t_end, op.cap, /*fresh=*/true,
                     &ctx->run_buffers);
      ctx->rx_copy_ns += t_end - t_copy;
      ctx->progress_ns += t_end - t_copy;
    }
    op.size = msg.buf.len;
    op.done = true;
    Buffer freed = std::move(msg.buf);
    box->second.pop_front();
    recycle_buffer(ctx, std::move(freed));
    return 1;
  }
  return -1;
}

// Bytes of a posted receive that lie in the caller's buffer, from its
// front, and are the message's for good (mt_send_written's mirror): what
// the drain has copied out of the ring of the message bound to the receive
// (Partial.filled moves after the copy has returned), the whole size once
// the receive is done, 0 while no message is bound to it or its message
// goes through an assembly buffer.  -1 for an unknown handle, and for a
// receive whose buffer holds the front of a message its sender abandoned
// (abandon_partials): the next message fills it from the front again, so
// what was said before no longer holds, and nothing is said until whoever
// reads has taken the buffer whole.
int64_t mt_recv_filled(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto rit = ctx->recvs.find(handle);
  if (rit == ctx->recvs.end() || rit->second.torn) return -1;
  const RecvOp& op = rit->second;
  if (op.done) return (int64_t)op.size;
  if (!op.bound) return 0;
  const Partial& part = ctx->partial.at({op.src, op.msg_id});
  return (int64_t)std::min(part.filled, part.total);
}

int64_t mt_recv_size(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto rit = ctx->recvs.find(handle);
  if (rit == ctx->recvs.end()) return -1;
  return (int64_t)rit->second.size;
}

void mt_cancel(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto sit = ctx->sends.find(handle);
  if (sit != ctx->sends.end()) {
    // Chunks already in the peer ring stay (the receiver discards partial
    // messages at finalize); the op stops producing more.
    sit->second.cancelled = true;
    ctx->sends.erase(sit);
    return;
  }
  auto rit = ctx->recvs.find(handle);
  if (rit != ctx->recvs.end()) drop_recv(ctx, rit);
}

// Forget a handle whose completion the caller has seen.
void mt_release(void* vctx, int64_t handle) {
  auto* ctx = static_cast<Ctx*>(vctx);
  ctx->recvs.erase(handle);
  ctx->sends.erase(handle);
}

// Bytes of the messages received whole so far: which == 0, those that
// landed in the buffer of a receive posted before they arrived; 1, those
// assembled in a buffer of the transport's own.
uint64_t mt_rx_bytes(void* vctx, int32_t which) {
  auto* ctx = static_cast<Ctx*>(vctx);
  return which == 0 ? ctx->rx_direct_bytes : ctx->rx_assembled_bytes;
}

// How the rings were used so far: which == 0, chunks this endpoint placed
// in its peers' rings; 1, placements a full ring refused (the sender waited
// for the owner); 2, chunks copied out of the own rings; 3, those of them
// during whose copy the ring's head moved: sender and owner were copying
// at the same time; 4, payload bytes placed while their op's pieces were
// short of its length; 5, buffers of copy intervals allocated (none while
// the timing is off); 6, payload bytes placed in parts copied at once by
// the caller and the helpers; 7, those copied out so (none without helpers).
uint64_t mt_ring_counts(void* vctx, int32_t which) {
  auto* ctx = static_cast<Ctx*>(vctx);
  const uint64_t counts[] = {ctx->tx_chunks,      ctx->tx_ring_full,
                             ctx->rx_chunks,      ctx->rx_overlap_chunks,
                             ctx->tx_early_bytes, ctx->run_buffers,
                             ctx->tx_split_bytes, ctx->rx_split_bytes};
  return which >= 0 && which < 8 ? counts[which] : 0;
}

// What this endpoint's unfinished transfers stand before, as bits, from the
// state the last pass of progress() left (no clock is read): 1, a send at
// the front of its queue with every appended byte placed and short of its
// length (its caller has staged no more: `unready`); 2, a front send with
// bytes left that the ring has no room for this pass (the owner's drain:
// `blocked`); 4, a receive posted with a buffer that no message has begun
// to land in (the peer has not begun to send); 8, a receive whose message
// is landing and is not whole (the sender's next chunks are not published).
int32_t mt_waiting(void* vctx) {
  auto* ctx = static_cast<Ctx*>(vctx);
  int32_t bits = 0;
  for (auto& [dst, queue] : ctx->send_q) {
    for (int64_t handle : queue) {
      auto it = ctx->sends.find(handle);
      if (it == ctx->sends.end() || it->second.cancelled || it->second.done) {
        continue;
      }
      const SendOp& op = it->second;
      bits |= op.appended == op.written && op.len > 0 ? 1 : 2;
      break;  // the front one: those behind it wait for it
    }
  }
  for (auto& [handle, op] : ctx->recvs) {
    if (op.done || op.cancelled) continue;
    bits |= op.bound ? 8 : 4;
  }
  return bits;
}

// The one switch of the wire's timing: on, every message keeps a record of
// where its time went (mt_op_timing), every chunk carries the instant it
// was published, and the endpoint its totals (mt_wire_ns); off (the
// default) the message path reads no clock.
void mt_set_timing(void* vctx, int32_t on) {
  auto* ctx = static_cast<Ctx*>(vctx);
  ctx->timing = on != 0;
  if (ctx->crew != nullptr) {
    ctx->crew->timed.store(ctx->timing, std::memory_order_relaxed);
  }
}

// The record of a finished op, for the caller whose mt_test saw it done
// and before mt_release: up to kTimingWords words into `out`, and how many
// were written; 0 without a record (timing off, a handle unknown or not
// done).  [0] 1 a send, 2 a receive; [1] the sender's msg_id; [2] t_first
// and [3] t_done, ns on CLOCK_MONOTONIC; [4] copy_ns; [5] blocked_ns of a
// send, starved_ns of a receive; [6] away_ns; [7] a receive's t_first_pub;
// [8] bytes; [9] a send's early_bytes and [10] its unready_ns (a part of
// [6]); [11] copy intervals that were merged over a gap (mt_op_intervals);
// [12] payload bytes copied in parts by the caller and the helpers.
constexpr int32_t kTimingWords = 13;

int32_t mt_op_timing(void* vctx, int64_t handle, void* vout) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto* out = static_cast<uint64_t*>(vout);
  auto sit = ctx->sends.find(handle);
  if (sit != ctx->sends.end()) {
    const SendOp& op = sit->second;
    const TxTiming& tt = op.tt;
    if (!op.done || tt.t_first == 0) return 0;
    const uint64_t busy = tt.copy_ns + tt.blocked_ns;
    const uint64_t words[kTimingWords] = {
        1, op.msg_id, tt.t_first, tt.t_done, tt.copy_ns, tt.blocked_ns,
        since(tt.t_done - tt.t_first, busy), tt.t_first, op.len,
        op.early_bytes, tt.unready_ns, tt.runs.merged, tt.split_bytes};
    std::memcpy(out, words, sizeof(words));
    return kTimingWords;
  }
  auto rit = ctx->recvs.find(handle);
  if (rit != ctx->recvs.end()) {
    const RecvOp& op = rit->second;
    const RxTiming& rt = op.rt;
    if (!op.done || rt.chunks == 0) return 0;
    const uint64_t words[kTimingWords] = {
        2, rt.msg_id, rt.t_first, rt.t_done, rt.copy_ns, rt.starved_ns,
        rt.away_ns, rt.t_first_pub, op.size, 0, 0, rt.runs.merged,
        rt.split_bytes};
    std::memcpy(out, words, sizeof(words));
    return kTimingWords;
  }
  return 0;
}

// The copy intervals of a finished op, for the same caller at the same
// time as mt_op_timing: (begin ns, end ns, bytes) of each, in order and
// apart, three words an interval into `out` (room for 3 * 64), and how
// many intervals were written; 0 without a record.  Their lengths sum to
// the record's copy_ns and what lay between the chunks of a run.
int32_t mt_op_intervals(void* vctx, int64_t handle, void* vout) {
  auto* ctx = static_cast<Ctx*>(vctx);
  auto* out = static_cast<uint64_t*>(vout);
  const CopyRuns* runs = nullptr;
  auto sit = ctx->sends.find(handle);
  auto rit = ctx->recvs.find(handle);
  if (sit != ctx->sends.end() && sit->second.done) {
    runs = &sit->second.tt.runs;
  } else if (rit != ctx->recvs.end() && rit->second.done) {
    runs = &rit->second.rt.runs;
  }
  if (runs == nullptr) return 0;
  for (const CopyRun& run : runs->runs) {
    *out++ = run.begin;
    *out++ = run.end;
    *out++ = run.bytes;
  }
  return (int32_t)runs->runs.size();
}

// The endpoint's totals while timing, ns: which == 0, inside circ_write;
// 1, inside circ_read and the memcpy that hands an assembled message
// over; 2, inside progress() and that memcpy; 3, the helpers' inside the
// parts they copied; 4, the helpers' spinning with no part to take (the
// wait for a chunk's next half, and `spin_ns` after the last: none of it
// asleep).  Cumulative: read as deltas.
uint64_t mt_wire_ns(void* vctx, int32_t which) {
  auto* ctx = static_cast<Ctx*>(vctx);
  const Crew* crew = ctx->crew;
  const uint64_t totals[] = {
      ctx->tx_copy_ns, ctx->rx_copy_ns, ctx->progress_ns,
      crew ? crew->copy_ns.load(std::memory_order_relaxed) : 0,
      crew ? crew->spun_ns.load(std::memory_order_relaxed) : 0};
  return which >= 0 && which < 5 ? totals[which] : 0;
}

// Monotonic wall clock in seconds (the MPI_Wtime analog,
// reference mpifuncs.c:2500-2513).
double mt_time(void) {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + 1e-9 * (double)ts.tv_nsec;
}

// -- wire-codec kernels (mpit_tpu/comm/codec.py hot paths) -------------------
//
// Single-translation-unit home for the codec inner loops: the numpy
// reference implementations in codec.py make ~8 full passes per tile
// (measured 0.66 s to int8-encode 640 MB with residual on the 1-core
// bench host), and on a host where the encoder competes with the wire
// for the same core that cost lands 1:1 on PS throughput.  These loops
// do the same math in 2 passes per 1024-element block (absmax, then
// quantize+residual) with block-cache-resident reads, ctypes releases
// the GIL for the duration, and codec.py keeps the numpy path as the
// fallback (and as the parity oracle in tests/test_codec.py).
//
// Float semantics match numpy exactly: scale = absmax/127 (1.0 for
// all-zero blocks), code = rintf(w * (1/scale)) (round-half-to-even,
// same as np.rint), residual = w - code*scale evaluated without fp
// contraction (build.py passes -ffp-contract=off) so native and numpy
// frames are bit-identical.

constexpr uint64_t kCodecBlock = 1024;  // == codec.BLOCK

void mt_codec_int8_encode(const void* vx, void* vresidual, uint64_t n,
                          void* vscales, void* vcodes) {
  const float* x = static_cast<const float*>(vx);
  float* r = static_cast<float*>(vresidual);  // nullable (param path)
  float* scales = static_cast<float*>(vscales);
  int8_t* codes = static_cast<int8_t*>(vcodes);
  uint64_t nb = (n + kCodecBlock - 1) / kCodecBlock;
  for (uint64_t b = 0; b < nb; ++b) {
    uint64_t lo = b * kCodecBlock;
    uint64_t hi = lo + kCodecBlock < n ? lo + kCodecBlock : n;
    float absmax = 0.0f;
    if (r != nullptr) {
      for (uint64_t i = lo; i < hi; ++i) {
        float w = x[i] + r[i];
        float a = fabsf(w);
        if (a > absmax) absmax = a;
      }
    } else {
      for (uint64_t i = lo; i < hi; ++i) {
        float a = fabsf(x[i]);
        if (a > absmax) absmax = a;
      }
    }
    float scale = absmax == 0.0f ? 1.0f : absmax / 127.0f;
    float inv = 1.0f / scale;
    scales[b] = scale;
    if (r != nullptr) {
      for (uint64_t i = lo; i < hi; ++i) {
        float w = x[i] + r[i];
        float q = rintf(w * inv);
        codes[i] = (int8_t)q;
        r[i] = w - q * scale;
      }
    } else {
      for (uint64_t i = lo; i < hi; ++i) {
        codes[i] = (int8_t)rintf(x[i] * inv);
      }
    }
  }
}

void mt_codec_int8_decode(const void* vscales, const void* vcodes, uint64_t n,
                          void* vout) {
  const float* scales = static_cast<const float*>(vscales);
  const int8_t* codes = static_cast<const int8_t*>(vcodes);
  float* out = static_cast<float*>(vout);
  uint64_t nb = (n + kCodecBlock - 1) / kCodecBlock;
  for (uint64_t b = 0; b < nb; ++b) {
    uint64_t lo = b * kCodecBlock;
    uint64_t hi = lo + kCodecBlock < n ? lo + kCodecBlock : n;
    float scale = scales[b];
    for (uint64_t i = lo; i < hi; ++i) {
      out[i] = (float)codes[i] * scale;
    }
  }
}

void mt_codec_bf16_encode(const void* vx, uint64_t n, void* vwire) {
  // Truncation: the high half-word of each little-endian fp32.
  const uint16_t* src = static_cast<const uint16_t*>(vx);
  uint16_t* dst = static_cast<uint16_t*>(vwire);
  for (uint64_t i = 0; i < n; ++i) {
    dst[i] = src[2 * i + 1];
  }
}

void mt_codec_bf16_decode(const void* vwire, uint64_t n, void* vout) {
  const uint16_t* src = static_cast<const uint16_t*>(vwire);
  uint32_t* dst = static_cast<uint32_t*>(vout);
  for (uint64_t i = 0; i < n; ++i) {
    dst[i] = (uint32_t)src[i] << 16;
  }
}

// -- data-plane kernels for the worker pool ----------------------------------
//
// The fused f32 add-fold (agg interior-node per-chunk fold): a
// single-pass replacement for a multi-pass numpy pipeline that must stay
// bit-identical to the numpy reference (tests/test_pool.py parity suite).
// It keeps numpy's association order
// ((own[i] + c0[i]) + c1[i]) + ... element-wise with -ffp-contract=off,
// so no FMA ever merges an add pair the serial path keeps separate.

// vptrs: uint64_t[nchildren] raw child-buffer addresses, each f32[n].
// The serial agg fold does copyto(acc, own) then one `acc += child` pass
// per child — nchildren+1 DRAM round trips over the chunk.  This fuses
// them into one read pass over every operand and one write pass, keeping
// the exact per-element association order of the serial loop.
void mt_fold_f32(const void* vown, const void* vptrs, int32_t nchildren,
                 void* vout, int64_t n) {
  const float* own = static_cast<const float*>(vown);
  const uint64_t* ptrs = static_cast<const uint64_t*>(vptrs);
  float* out = static_cast<float*>(vout);
  for (int64_t i = 0; i < n; ++i) {
    float acc = own[i];
    for (int32_t c = 0; c < nchildren; ++c) {
      acc += reinterpret_cast<const float*>((uintptr_t)ptrs[c])[i];
    }
    out[i] = acc;
  }
}

// Bumped whenever specs/*.json and this file change together; the
// generated _bindings.py refuses a stale .so (loud rebuild message)
// instead of failing with a confusing missing-symbol AttributeError.
// Keep in sync with MT_API_VERSION in gen_bindings.py.
int64_t mt_api_version(void) { return 17010; }

}  // extern "C"

// -- worker-pool data plane --------------------------------------------------
//
// A persistent native thread pool so chunk encode/decode/fold runs off
// the Python critical thread (otherwise one interpreter lock caps it).  Jobs
// are pure: owned input pointers -> owned output pointers, all regions
// disjoint per job, per-block int8 EF state (the residual slice) carried in
// the job.  Completion order therefore never influences byte content; the
// Python seam (mpit_tpu/comm/pool.py) collects results in submission order.

namespace {

enum PoolJobKind {
  kJobInt8Enc = 1,
  kJobInt8Dec = 2,
  kJobBf16Enc = 3,
  kJobBf16Dec = 4,
  // 5 is retired (it was the byte-wise XOR) and not reused
  kJobFoldF32 = 6,
  kJobCopy = 7,
};
constexpr int32_t kJobKinds = 8;  // valid kinds are 1..kJobKinds-1

struct PoolJob {
  uint64_t handle = 0;
  int32_t kind = 0;
  const void* a = nullptr;  // primary input
  const void* b = nullptr;  // secondary input (residual / ptrs)
  void* c = nullptr;        // primary output
  void* d = nullptr;        // secondary output (int8 codes)
  int64_t n = 0;
  int64_t aux = 0;                // fold: nchildren
  std::vector<uint64_t> ptrs;     // fold: owned copy of child addresses
};

struct Pool {
  std::mutex mu;
  std::condition_variable cv_work;  // workers: queue non-empty or closing
  std::condition_variable cv_done;  // waiters: a job completed
  std::deque<PoolJob> queue;
  std::map<uint64_t, int> state;  // handle -> 0 pending, 1 done
  std::vector<std::thread> threads;
  uint64_t next_handle = 1;
  bool closing = false;
  int64_t running = 0;
  uint64_t jobs_by_kind[kJobKinds] = {0};
  std::atomic<uint64_t> busy_ns{0};
};

void pool_run(const PoolJob& job) {
  switch (job.kind) {
    case kJobInt8Enc:
      mt_codec_int8_encode(job.a, const_cast<void*>(job.b), (uint64_t)job.n,
                           job.c, job.d);
      break;
    case kJobInt8Dec:
      mt_codec_int8_decode(job.a, job.b, (uint64_t)job.n, job.c);
      break;
    case kJobBf16Enc:
      mt_codec_bf16_encode(job.a, (uint64_t)job.n, job.c);
      break;
    case kJobBf16Dec:
      mt_codec_bf16_decode(job.a, (uint64_t)job.n, job.c);
      break;
    case kJobFoldF32:
      mt_fold_f32(job.a, job.ptrs.data(), (int32_t)job.aux, job.c, job.n);
      break;
    case kJobCopy:
      memcpy(job.c, job.a, (size_t)job.n);
      break;
    default:
      break;
  }
}

void pool_worker(Pool* pool) {
  for (;;) {
    PoolJob job;
    {
      std::unique_lock<std::mutex> lk(pool->mu);
      pool->cv_work.wait(
          lk, [pool] { return pool->closing || !pool->queue.empty(); });
      if (pool->queue.empty()) return;  // closing and fully drained
      job = std::move(pool->queue.front());
      pool->queue.pop_front();
      pool->running++;
    }
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    pool_run(job);
    clock_gettime(CLOCK_MONOTONIC, &t1);
    uint64_t ns = (uint64_t)(t1.tv_sec - t0.tv_sec) * 1000000000ull +
                  (uint64_t)(t1.tv_nsec - t0.tv_nsec);
    {
      std::lock_guard<std::mutex> lk(pool->mu);
      pool->running--;
      pool->state[job.handle] = 1;
      pool->jobs_by_kind[job.kind]++;
      pool->busy_ns.fetch_add(ns, std::memory_order_relaxed);
    }
    pool->cv_done.notify_all();
  }
}

}  // namespace

extern "C" {

// Spawn a pool with nthreads workers; NULL when nthreads <= 0 (callers
// treat that as "stay serial").  Pools are instance-scoped like mt_init
// contexts so tests can run several geometries side by side.
void* mt_pool_start(int32_t nthreads) {
  if (nthreads <= 0) return nullptr;
  Pool* pool = new Pool();
  pool->threads.reserve((size_t)nthreads);
  for (int32_t i = 0; i < nthreads; ++i) {
    pool->threads.emplace_back(pool_worker, pool);
  }
  return pool;
}

// Drain every queued job, join all workers, free the pool.  Submitting to
// a closed pool is the caller's error (the Python seam raises before it
// can reach a freed pointer).
void mt_pool_close(void* vpool) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr) return;
  {
    std::lock_guard<std::mutex> lk(pool->mu);
    pool->closing = true;
  }
  pool->cv_work.notify_all();
  for (auto& t : pool->threads) t.join();
  delete pool;
}

int32_t mt_pool_threads(void* vpool) {
  auto* pool = static_cast<Pool*>(vpool);
  return pool == nullptr ? 0 : (int32_t)pool->threads.size();
}

// Enqueue one pure job; returns a handle (> 0), or 0 when the pool is
// closing or the job is malformed.  Operand meaning by kind:
//   INT8_ENC  a=x f32[n], b=residual f32[n]|NULL, c=scales, d=codes
//   INT8_DEC  a=scales, b=codes, c=out f32[n]
//   BF16_ENC  a=x f32[n], c=wire u16[n]      BF16_DEC a=wire, c=out
//   FOLD_F32  a=own f32[n], b=u64[aux] child addresses (copied), c=out
//   COPY      a=src, c=dst, n bytes
// Buffers must stay alive until the job completes (zero-copy rule; the
// Python Job object holds the references).
uint64_t mt_pool_submit(void* vpool, int32_t kind, const void* a,
                        const void* b, void* c, void* d, int64_t n,
                        int64_t aux) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr || kind <= 0 || kind >= kJobKinds || n < 0 ||
      kind == 5 /* retired */) {
    return 0;
  }
  PoolJob job;
  job.kind = kind;
  job.a = a;
  job.b = b;
  job.c = c;
  job.d = d;
  job.n = n;
  job.aux = aux;
  if (kind == kJobFoldF32) {
    if (b == nullptr || aux < 0) return 0;
    const uint64_t* ptrs = static_cast<const uint64_t*>(b);
    job.ptrs.assign(ptrs, ptrs + aux);  // owned copy: caller may free b
  }
  uint64_t handle;
  {
    std::lock_guard<std::mutex> lk(pool->mu);
    if (pool->closing) return 0;
    handle = pool->next_handle++;
    job.handle = handle;
    pool->state[handle] = 0;
    pool->queue.push_back(std::move(job));
  }
  pool->cv_work.notify_one();
  return handle;
}

// 1 done (handle retired), 0 pending, -1 unknown.
int32_t mt_pool_poll(void* vpool, uint64_t handle) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr) return -1;
  std::lock_guard<std::mutex> lk(pool->mu);
  auto it = pool->state.find(handle);
  if (it == pool->state.end()) return -1;
  if (it->second == 0) return 0;
  pool->state.erase(it);
  return 1;
}

// Block until the job completes (ctypes drops the GIL for the duration);
// 0 ok (handle retired), -1 unknown.
int32_t mt_pool_wait(void* vpool, uint64_t handle) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr) return -1;
  std::unique_lock<std::mutex> lk(pool->mu);
  auto it = pool->state.find(handle);
  if (it == pool->state.end()) return -1;
  pool->cv_done.wait(lk, [pool, handle] {
    auto jt = pool->state.find(handle);
    return jt == pool->state.end() || jt->second == 1;
  });
  pool->state.erase(handle);
  return 0;
}

// Jobs submitted but not yet finished (queued + running).
int64_t mt_pool_depth(void* vpool) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr) return 0;
  std::lock_guard<std::mutex> lk(pool->mu);
  return (int64_t)pool->queue.size() + pool->running;
}

// Completed-job count for one kind, or the total when kind == 0.
uint64_t mt_pool_jobs(void* vpool, int32_t kind) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr || kind < 0 || kind >= kJobKinds) return 0;
  std::lock_guard<std::mutex> lk(pool->mu);
  if (kind != 0) return pool->jobs_by_kind[kind];
  uint64_t total = 0;
  for (int32_t k = 1; k < kJobKinds; ++k) total += pool->jobs_by_kind[k];
  return total;
}

// Cumulative worker seconds spent inside kernels.
double mt_pool_busy_seconds(void* vpool) {
  auto* pool = static_cast<Pool*>(vpool);
  if (pool == nullptr) return 0.0;
  return 1e-9 * (double)pool->busy_ns.load(std::memory_order_relaxed);
}

}  // extern "C"
