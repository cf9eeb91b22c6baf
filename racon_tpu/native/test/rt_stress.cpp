// Concurrency stress harness for the native runtime, meant to run under
// the sanitizer builds (`make tsan|asan|ubsan`). Where rt_test.cpp checks
// functional behaviour, this file hammers the concurrent seams:
//
//   1. submit storm        — many producer threads racing submit()
//   2. shutdown w/ backlog — destructor drains a loaded queue
//   3. mid-flight cancel   — cancel_pending() vs running workers; dropped
//                            futures must break, not hang; pool reusable
//   4. pool churn          — rapid create/submit/destroy cycles
//   4b. blocked loop       — parallel_for: every index once from plain
//                            (unsynchronised) per-index writes, throwing
//                            items, and a cancel_pending() racing its
//                            runners: returns or throws, never hangs
//   5. CIGAR install race  — concurrent set_job_cigar on disjoint jobs,
//                            then pooled host alignment for the rest
//                            (the device/host alignment hand-off)
//   6. consensus hand-off  — device-style set_consensus installs racing
//                            host consensus_cpu_one on disjoint windows
//                            (the device/host consensus hand-off; one
//                            external consensus caller only — that thread
//                            owns the shared aligner slot n)
//   6b. overlapped fallback — the consensus driver's interleaving: one
//                            external thread hands rejected windows to
//                            pool workers (consensus_cpu_submit) and
//                            meanwhile exports, parity-checks and
//                            installs other windows, then joins once
//
// Build + run:  make -C racon_tpu/native stress   (or tsan/asan/ubsan)
#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <fstream>
#include <future>
#include <string>
#include <thread>
#include <utility>
#include <vector>

#include "../src/rt_pipeline.hpp"
#include "../src/rt_threadpool.hpp"

// Atomic because CHECKs fire from racer threads too.
static std::atomic<int> g_failures{0};
static std::atomic<int> g_checks{0};

#define CHECK(cond)                                                        \
  do {                                                                     \
    ++g_checks;                                                            \
    if (!(cond)) {                                                         \
      ++g_failures;                                                        \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
    }                                                                      \
  } while (0)

#define CHECK_EQ(a, b)                                                     \
  do {                                                                     \
    ++g_checks;                                                            \
    auto va = (a);                                                         \
    auto vb = (b);                                                         \
    if (!(va == vb)) {                                                     \
      ++g_failures;                                                        \
      std::fprintf(stderr, "FAIL %s:%d: %s != %s\n", __FILE__, __LINE__,   \
                   #a, #b);                                                \
    }                                                                      \
  } while (0)

static std::string g_tmpdir;

static std::string write_file(const std::string& name,
                              const std::string& content) {
  const std::string path = g_tmpdir + "/" + name;
  std::ofstream(path) << content;
  return path;
}

// ---- 1. submit storm -------------------------------------------------------
// Many producers race submit() against 4 workers; every future resolves and
// every job runs exactly once. Producers also probe this_thread_index()
// concurrently — non-pool callers must all map to the shared slot n.
static void stress_submit_storm() {
  constexpr int kProducers = 8;
  constexpr int kJobsPerProducer = 200;
  rt::ThreadPool pool(4);
  std::atomic<int> ran{0};
  std::vector<std::thread> producers;
  producers.reserve(kProducers);
  for (int p = 0; p < kProducers; ++p) {
    producers.emplace_back([&pool, &ran] {
      CHECK_EQ(pool.this_thread_index(), pool.num_threads());
      std::vector<std::future<void>> futs;
      futs.reserve(kJobsPerProducer);
      for (int i = 0; i < kJobsPerProducer; ++i) {
        futs.emplace_back(pool.submit([&ran] {
          ran.fetch_add(1, std::memory_order_relaxed);
        }));
      }
      for (auto& f : futs) {
        f.get();
      }
    });
  }
  for (auto& t : producers) {
    t.join();
  }
  CHECK_EQ(ran.load(), kProducers * kJobsPerProducer);
}

// ---- 2. shutdown with a loaded queue --------------------------------------
// The destructor must let workers drain everything already queued; no job
// is lost and no worker pops from a destructed queue.
static void stress_shutdown_backlog() {
  std::atomic<int> ran{0};
  constexpr int kJobs = 1000;
  {
    rt::ThreadPool pool(4);
    for (int i = 0; i < kJobs; ++i) {
      pool.submit([&ran] { ran.fetch_add(1, std::memory_order_relaxed); });
    }
    // destructor runs here with most of the queue still pending
  }
  CHECK_EQ(ran.load(), kJobs);
}

// ---- 3. mid-flight cancellation -------------------------------------------
// cancel_pending() from another thread while workers chew slow jobs: every
// submitted job either ran or its future throws broken_promise, the two
// counts add up, and the pool keeps working afterwards.
static void stress_cancellation() {
  rt::ThreadPool pool(2);
  std::atomic<int> ran{0};
  constexpr int kJobs = 64;
  std::vector<std::future<void>> futs;
  futs.reserve(kJobs);
  for (int i = 0; i < kJobs; ++i) {
    futs.emplace_back(pool.submit([&ran] {
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
      ran.fetch_add(1, std::memory_order_relaxed);
    }));
  }
  std::size_t dropped = 0;
  std::thread canceller([&pool, &dropped] {
    std::this_thread::sleep_for(std::chrono::milliseconds(5));
    dropped = pool.cancel_pending();
  });
  canceller.join();
  int broken = 0;
  for (auto& f : futs) {
    try {
      f.get();
    } catch (const std::future_error&) {
      ++broken;
    }
  }
  CHECK_EQ(static_cast<std::size_t>(broken), dropped);
  CHECK_EQ(ran.load() + broken, kJobs);
  // the pool survives a cancellation and still serves new work
  std::atomic<int> again{0};
  std::vector<std::future<void>> futs2;
  for (int i = 0; i < 8; ++i) {
    futs2.emplace_back(pool.submit([&again] {
      again.fetch_add(1, std::memory_order_relaxed);
    }));
  }
  for (auto& f : futs2) {
    f.get();
  }
  CHECK_EQ(again.load(), 8);
}

// ---- 4. pool churn ---------------------------------------------------------
// Rapid create/submit/destroy cycles: constructor/worker-startup and
// destructor/worker-drain handshakes under repetition.
static void stress_pool_churn() {
  std::atomic<int> ran{0};
  constexpr int kCycles = 20;
  constexpr int kJobs = 50;
  for (int c = 0; c < kCycles; ++c) {
    rt::ThreadPool pool(4);
    for (int i = 0; i < kJobs; ++i) {
      pool.submit([&ran] { ran.fetch_add(1, std::memory_order_relaxed); });
    }
  }
  CHECK_EQ(ran.load(), kCycles * kJobs);
}

// ---- 4b. blocked parallel-for ----------------------------------------------
// Plain per-index writes (a second visit of an index from another thread
// is a race the sanitizer reports, as the pipeline's per-object writes
// would be); items that throw: the lowest index is the one rethrown, after
// every runner has ended, so the by-reference captures are still alive;
// cancel_pending() against queued runners: the call ends either way and
// no index runs twice.
static void stress_parallel_for() {
  rt::ThreadPool pool(4);
  for (uint64_t n : {0ull, 1ull, 3ull, 64ull, 65ull, 1000ull, 100000ull}) {
    std::vector<uint32_t> visits(n, 0);
    const uint32_t tasks =
        pool.parallel_for(n, [&visits](uint64_t i) { ++visits[i]; });
    CHECK(tasks <= pool.num_threads());
    CHECK(n == 0 || tasks >= 1);
    uint64_t once = 0;
    for (uint32_t v : visits) {
      once += v == 1;
    }
    CHECK_EQ(once, n);
  }
  for (uint64_t fail_from : {0ull, 777ull, 9999ull}) {
    std::vector<uint32_t> visits(10000, 0);
    uint64_t thrown = ~0ull;
    try {
      pool.parallel_for(visits.size(), [&visits, fail_from](uint64_t i) {
        ++visits[i];
        if (i >= fail_from) {
          throw i;
        }
      });
    } catch (uint64_t i) {
      thrown = i;
    }
    CHECK_EQ(thrown, fail_from);
    bool ran_as_it_should = true;  // all up to the failure, none twice
    for (uint64_t i = 0; i < visits.size(); ++i) {
      ran_as_it_should &= visits[i] <= 1 && (i > fail_from || visits[i] == 1);
    }
    CHECK(ran_as_it_should);
  }
  for (int round = 0; round < 50; ++round) {
    rt::ThreadPool small(2);
    std::vector<uint32_t> visits(4096, 0);
    std::thread canceller([&small] { small.cancel_pending(); });
    bool whole = true;
    try {
      small.parallel_for(visits.size(), [&visits](uint64_t i) { ++visits[i]; });
    } catch (const std::future_error&) {
      whole = false;  // a runner was dropped before it began
    }
    canceller.join();
    bool none_twice = true;
    for (uint32_t v : visits) {
      none_twice &= v <= 1 && (!whole || v == 1);
    }
    CHECK(none_twice);
  }
}

// ---- pipeline fixtures -----------------------------------------------------

// Deterministic pseudo-random truth (same generator as rt_test.cpp, longer
// so the pipeline has enough windows/jobs to race over).
static std::string make_truth(int length) {
  std::string truth;
  uint64_t x = 0x9E3779B97F4A7C15ull;
  for (int i = 0; i < length; ++i) {
    x ^= x << 13;
    x ^= x >> 7;
    x ^= x << 17;
    truth += "ACGT"[x & 3];
  }
  return truth;
}

static std::string make_draft(const std::string& truth) {
  std::string draft = truth;
  for (size_t i = 50; i < draft.size(); i += 100) {
    draft[i] = draft[i] == 'A' ? 'C' : 'A';
  }
  return draft;
}

// ---- 5. concurrent CIGAR installs -----------------------------------------
// PAF input (no CIGARs) so every overlap is an alignment job; several
// installer threads stamp device-style CIGARs onto disjoint jobs while the
// pool host-aligns the rest, mirroring the device/host alignment hand-off.
static void stress_cigar_install() {
  const int kLen = 6000;
  const int kReads = 8;
  const std::string truth = make_truth(kLen);
  const std::string draft = make_draft(truth);

  std::string reads, paf;
  for (int i = 0; i < kReads; ++i) {
    const std::string rn = "r" + std::to_string(i);
    reads += ">" + rn + "\n" + truth + "\n";
    paf += rn + "\t" + std::to_string(kLen) + "\t0\t" + std::to_string(kLen) +
           "\t+\ttgt\t" + std::to_string(kLen) + "\t0\t" +
           std::to_string(kLen) + "\t" + std::to_string(kLen - 60) + "\t" +
           std::to_string(kLen) + "\t60\n";
  }
  const std::string reads_p = write_file("cig_reads.fasta", reads);
  const std::string paf_p = write_file("cig_ovl.paf", paf);
  const std::string tgt_p = write_file("cig_tgt.fasta", ">tgt\n" + draft + "\n");

  rt::PipelineParams params;
  params.window_length = 500;
  params.num_threads = 4;
  rt::Pipeline pipe(reads_p, paf_p, tgt_p, params);
  pipe.prepare();
  const size_t n_jobs = pipe.num_align_jobs();
  CHECK_EQ(n_jobs, static_cast<size_t>(kReads));

  // Device installers: two threads stamp perfect-match CIGARs onto
  // disjoint halves of the even jobs; odd jobs are left for the host.
  const std::string cigar = std::to_string(kLen) + "M";
  std::vector<std::thread> installers;
  for (int half = 0; half < 2; ++half) {
    installers.emplace_back([&pipe, &cigar, half, n_jobs] {
      for (size_t j = half * 2; j < n_jobs; j += 4) {
        const char *q, *t;
        uint32_t q_len, t_len;
        pipe.align_job_views(j, &q, &q_len, &t, &t_len);
        CHECK(q_len > 0 && t_len > 0);
        pipe.set_job_cigar(j, cigar);
      }
    });
  }
  for (auto& t : installers) {
    t.join();
  }
  pipe.align_jobs_cpu();  // host finishes the odd jobs on the pool
  pipe.build_windows();
  CHECK(pipe.num_windows() > 0);
  pipe.consensus_cpu_all();
  std::vector<std::pair<std::string, std::string>> out;
  pipe.stitch(true, &out);
  CHECK_EQ(out.size(), 1u);
  CHECK_EQ(out[0].second, truth);
}

// ---- 6. consensus hand-off -------------------------------------------------
// Device-style installs (set_consensus from installer threads) racing host
// consensus (consensus_cpu_one from one external thread) on disjoint
// windows — the overlap-free interleaving the drivers rely on. Exactly one
// external consensus caller: that thread owns the shared aligner slot n.
static void stress_consensus_handoff() {
  const int kLen = 6000;
  const std::string truth = make_truth(kLen);
  const std::string draft = make_draft(truth);

  std::string reads, sam = "@HD\tVN:1.6\n@SQ\tSN:tgt\tLN:" +
                           std::to_string(kLen) + "\n";
  for (int i = 0; i < 5; ++i) {
    const std::string rn = "r" + std::to_string(i);
    reads += ">" + rn + "\n" + truth + "\n";
    sam += rn + "\t0\ttgt\t1\t60\t" + std::to_string(kLen) + "M\t*\t0\t0\t" +
           truth + "\t*\n";
  }
  const std::string reads_p = write_file("con_reads.fasta", reads);
  const std::string sam_p = write_file("con_ovl.sam", sam);
  const std::string tgt_p = write_file("con_tgt.fasta", ">tgt\n" + draft + "\n");

  rt::PipelineParams params;
  params.window_length = 200;
  params.match = 5;
  params.mismatch = -4;
  params.gap = -8;
  params.num_threads = 4;
  rt::Pipeline pipe(reads_p, sam_p, tgt_p, params);
  pipe.initialize();
  const size_t n = pipe.num_windows();
  CHECK_EQ(n, static_cast<size_t>(kLen / 200));

  // Installer threads serve even windows with the device result (here: the
  // truth slice the POA would converge to); one external host thread
  // serves the odd windows.
  std::vector<std::thread> racers;
  for (int half = 0; half < 2; ++half) {
    racers.emplace_back([&pipe, &truth, half, n] {
      for (size_t i = half * 2; i < n; i += 4) {
        pipe.set_consensus(i, truth.substr(i * 200, 200), true);
      }
    });
  }
  racers.emplace_back([&pipe, n] {
    for (size_t i = 1; i < n; i += 2) {
      CHECK(pipe.consensus_cpu_one(i));
    }
  });
  for (auto& t : racers) {
    t.join();
  }
  for (size_t i = 0; i < n; ++i) {
    CHECK(pipe.has_consensus(i));
  }
  std::vector<std::pair<std::string, std::string>> out;
  pipe.stitch(true, &out);
  CHECK_EQ(out.size(), 1u);
  CHECK_EQ(out[0].second, truth);
}

// ---- 6b. overlapped host fallback -------------------------------------------
// What ops/poa_driver.py does since the fallback left the critical path:
// the driver thread submits device-rejected windows to the pool
// (consensus_cpu_submit: each worker owns its aligner slot) and goes on,
// on *other* windows, reading them for export (the fields
// rt_pipeline_window_export reads), running the sanitizer's sampled parity
// consensus (the one external consensus_cpu_one caller, slot n) and
// installing device results (set_consensus); one join at the end.
static uint64_t export_like(const rt::Window& w) {
  uint64_t sum = w.rank + w.id;
  for (size_t k = 0; k < w.sequences.size(); ++k) {
    const uint32_t len = w.sequences[k].second;
    for (uint32_t p = 0; p < len; ++p) {
      sum += static_cast<uint8_t>(w.sequences[k].first[p]);
      if (w.qualities[k].first != nullptr) {
        sum += static_cast<uint8_t>(w.qualities[k].first[p]);
      }
    }
    sum += w.positions[k].first + w.positions[k].second;
  }
  return sum;
}

static void stress_overlapped_fallback() {
  const int kLen = 12000;
  const std::string truth = make_truth(kLen);
  const std::string draft = make_draft(truth);

  std::string reads, sam = "@HD\tVN:1.6\n@SQ\tSN:tgt\tLN:" +
                           std::to_string(kLen) + "\n";
  for (int i = 0; i < 5; ++i) {
    const std::string rn = "r" + std::to_string(i);
    reads += ">" + rn + "\n" + truth + "\n";
    sam += rn + "\t0\ttgt\t1\t60\t" + std::to_string(kLen) + "M\t*\t0\t0\t" +
           truth + "\t*\n";
  }
  const std::string reads_p = write_file("ovf_reads.fasta", reads);
  const std::string sam_p = write_file("ovf_ovl.sam", sam);
  const std::string tgt_p = write_file("ovf_tgt.fasta", ">tgt\n" + draft + "\n");

  for (uint32_t threads : {1u, 4u}) {
    rt::PipelineParams params;
    params.window_length = 200;
    params.match = 5;
    params.mismatch = -4;
    params.gap = -8;
    params.num_threads = threads;
    rt::Pipeline pipe(reads_p, sam_p, tgt_p, params);
    pipe.initialize();
    const size_t n = pipe.num_windows();
    CHECK_EQ(n, static_cast<size_t>(kLen / 200));
    CHECK_EQ(pipe.consensus_cpu_join(), 0u);  // nothing submitted: no wait

    size_t submitted = 0, finished = 0;
    uint64_t exported = 0;
    std::thread driver([&] {
      for (size_t i = 0; i < n; ++i) {
        if (i % 3 == 1) {  // "device-rejected": to a pool worker, at once
          pipe.consensus_cpu_submit(i);
          ++submitted;
          continue;
        }
        exported += export_like(pipe.window(i));
        if (i % 6 == 0) {  // sampled parity, then the device result lands
          CHECK(pipe.consensus_cpu_one(i));
        }
        pipe.set_consensus(i, truth.substr(i * 200, 200), true);
      }
      finished = pipe.consensus_cpu_join();
    });
    driver.join();
    CHECK(exported != 0);
    CHECK_EQ(submitted, n / 3);
    CHECK(finished <= submitted);
    for (size_t i = 0; i < n; ++i) {
      CHECK(pipe.has_consensus(i));
      CHECK(pipe.is_polished(i));
    }
    std::vector<std::pair<std::string, std::string>> out;
    pipe.stitch(true, &out);
    CHECK_EQ(out.size(), 1u);
    CHECK_EQ(out[0].second, truth);
  }
}

int main() {
  g_tmpdir = "/tmp/rt_stress_" + std::to_string(::getpid());
  ::mkdir(g_tmpdir.c_str(), 0755);
  stress_submit_storm();
  stress_shutdown_backlog();
  stress_cancellation();
  stress_pool_churn();
  stress_parallel_for();
  stress_cigar_install();
  stress_consensus_handoff();
  stress_overlapped_fallback();
  if (g_failures.load()) {
    std::fprintf(stderr, "%d/%d stress checks FAILED (artifacts in %s)\n",
                 g_failures.load(), g_checks.load(), g_tmpdir.c_str());
    return 1;
  }
  std::system(("rm -rf '" + g_tmpdir + "'").c_str());
  std::printf("all %d stress checks passed\n", g_checks.load());
  return 0;
}
