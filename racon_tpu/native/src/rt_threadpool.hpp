// Minimal work-stealing-free thread pool with futures and a per-thread index
// map (so each worker can own a reusable POA aligner, the way the reference
// gives each thread its own spoa engine — /root/reference/src/polisher.cpp:
// 176,179-183,497-503). New implementation, parity with the vendored
// thread_pool library's Submit/thread_map surface.
#pragma once

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstddef>
#include <cstdint>
#include <exception>
#include <functional>
#include <future>
#include <mutex>
#include <queue>
#include <stdexcept>
#include <thread>
#include <unordered_map>
#include <vector>

namespace rt {

class ThreadPool {
 public:
  explicit ThreadPool(uint32_t num_threads) {
    num_threads = num_threads == 0 ? 1 : num_threads;
    for (uint32_t i = 0; i < num_threads; ++i) {
      workers_.emplace_back([this] { loop(); });
    }
    // thread_map_ is filled after the workers start, but workers only read
    // it from inside a job, and every job is handed over through mutex_:
    // the ctor's writes happen-before submit()'s lock release on the
    // submitting thread, which happens-before the worker's lock acquire.
    // After the ctor the map is never mutated, so lock-free reads in
    // this_thread_index() are safe.
    for (uint32_t i = 0; i < num_threads; ++i) {
      thread_map_[workers_[i].get_id()] = i;
    }
  }

  // Shutdown: the stop flag is set under the queue lock (a worker between
  // its predicate check and cv_.wait can never miss the notify), workers
  // drain whatever is still queued, then exit.
  ~ThreadPool() {
    {
      std::unique_lock<std::mutex> lock(mutex_);
      done_ = true;
    }
    cv_.notify_all();
    for (auto& w : workers_) {
      w.join();
    }
  }

  template <typename F>
  std::future<void> submit(F&& f) {
    auto task = std::make_shared<std::packaged_task<void()>>(std::forward<F>(f));
    auto fut = task->get_future();
    {
      std::unique_lock<std::mutex> lock(mutex_);
      if (done_) {
        // A task enqueued after shutdown began would be destroyed unrun
        // while its future blocks forever; refuse loudly instead.
        throw std::runtime_error("rt::ThreadPool: submit after shutdown");
      }
      queue_.emplace([task] { (*task)(); });
    }
    cv_.notify_one();
    return fut;
  }

  // Blocked parallel-for: fn(i) runs exactly once for every i in [0, n) and
  // the call returns when all are done. For loops whose items cost less
  // than a submit() (a lock, a notify and three allocations, ~12 us an
  // item under 13 workers): at most num_threads() runner tasks are
  // enqueued, and each takes blocks of consecutive indices from one
  // atomic cursor until the range is spent, so the queue is touched
  // O(threads) times, not O(n). The block is n / (16 x threads) items,
  // at least 1 (n <= 16 x threads: an item a block, as submit() per item
  // was) and at most 256 (no long tail behind one slow block).
  // An item that throws ends its runner's block and no new block is
  // taken; blocks already taken run on, so the failing item with the
  // lowest index always runs, and its exception is the one rethrown here,
  // after every runner has ended (what fn captured by reference outlives
  // them all). A runner cancel_pending() dropped surfaces as its
  // future's broken_promise unless an item failed first.
  // Returns the number of runner tasks enqueued.
  template <typename F>
  uint32_t parallel_for(uint64_t n, F&& fn) {
    const uint64_t threads = num_threads();
    const uint64_t block =
        std::min<uint64_t>(256, std::max<uint64_t>(1, n / (16 * threads)));
    const uint64_t runners = std::min(threads, (n + block - 1) / block);
    std::atomic<uint64_t> cursor{0};
    std::atomic<bool> failed{false};
    std::mutex error_mutex;
    uint64_t error_index = n;
    std::exception_ptr error;
    auto runner = [&] {
      while (!failed.load(std::memory_order_relaxed)) {
        const uint64_t begin = cursor.fetch_add(block);
        if (begin >= n) {
          return;
        }
        const uint64_t end = std::min(n, begin + block);
        for (uint64_t i = begin; i < end; ++i) {
          try {
            fn(i);
          } catch (...) {
            failed.store(true, std::memory_order_relaxed);
            std::unique_lock<std::mutex> lock(error_mutex);
            if (i < error_index) {
              error_index = i;
              error = std::current_exception();
            }
            return;
          }
        }
      }
    };
    std::vector<std::future<void>> futs;
    futs.reserve(runners);
    std::exception_ptr dropped;
    try {
      for (uint64_t r = 0; r < runners; ++r) {
        futs.emplace_back(submit(runner));
      }
    } catch (...) {
      // shutdown began: wait for the runners already enqueued, then say so
      dropped = std::current_exception();
    }
    for (auto& f : futs) {
      try {
        f.get();
      } catch (...) {
        dropped = std::current_exception();
      }
    }
    if (error || dropped) {
      std::rethrow_exception(error ? error : dropped);
    }
    return static_cast<uint32_t>(runners);
  }

  // Mid-flight cancellation: drop every job no worker has picked up yet.
  // Returns the number dropped. The dropped packaged_tasks are destroyed
  // unrun outside the lock, so their futures throw std::future_error
  // (broken_promise) — callers awaiting cancelled work unblock with an
  // error instead of hanging. Jobs already running are unaffected and the
  // pool stays usable.
  std::size_t cancel_pending() {
    std::queue<std::function<void()>> dropped;
    {
      std::unique_lock<std::mutex> lock(mutex_);
      dropped.swap(queue_);
    }
    return dropped.size();
  }

  uint32_t num_threads() const { return static_cast<uint32_t>(workers_.size()); }

  // Index of the calling thread: workers get 0..n-1; any non-pool caller
  // (e.g. the Python driver finishing device-rejected work) gets the
  // dedicated slot n, so its scratch state never races a worker's.
  uint32_t this_thread_index() const {
    auto it = thread_map_.find(std::this_thread::get_id());
    return it == thread_map_.end() ? static_cast<uint32_t>(workers_.size())
                                   : it->second;
  }

 private:
  void loop() {
    while (true) {
      std::function<void()> job;
      {
        std::unique_lock<std::mutex> lock(mutex_);
        // Explicit wait loop: the stop flag and the queue are re-checked
        // under the lock after every wake-up, so a spurious wake, a
        // cancel_pending() draining the queue between notify and wake, or
        // a shutdown racing a submit can never pop from an empty queue or
        // miss the stop request.
        while (!done_ && queue_.empty()) {
          cv_.wait(lock);
        }
        if (queue_.empty()) {
          return;  // stop requested and no work left to drain
        }
        job = std::move(queue_.front());
        queue_.pop();
      }
      job();
    }
  }

  std::vector<std::thread> workers_;
  std::unordered_map<std::thread::id, uint32_t> thread_map_;
  std::queue<std::function<void()>> queue_;
  std::mutex mutex_;
  std::condition_variable cv_;
  bool done_ = false;
};

}  // namespace rt
