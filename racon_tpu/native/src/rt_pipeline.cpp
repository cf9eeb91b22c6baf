#include "rt_error.hpp"
#include "rt_pipeline.hpp"

#include "rt_align.hpp"

#include <algorithm>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <exception>
#include <future>
#include <unordered_map>

namespace rt {

namespace {
constexpr uint64_t kChunkSize = 1024ull * 1024 * 1024;  // 1 GiB
}

Pipeline::Pipeline(const std::string& sequences_path,
                   const std::string& overlaps_path,
                   const std::string& target_path,
                   const PipelineParams& params)
    : params_(params) {
  if (params_.type != 0 && params_.type != 1) {
    rt::fail("[racon_tpu::createPolisher] error: invalid polisher type!\n");
  }
  if (params_.window_length == 0) {
    rt::fail("[racon_tpu::createPolisher] error: invalid window length!\n");
  }

  SeqFormat sfmt, tfmt;
  OvlFormat ofmt;
  if (!sniff_sequence_format(sequences_path, &sfmt)) {
    rt::fail("[racon_tpu::createPolisher] error: file %s has unsupported "
                 "format extension (valid extensions: .fasta, .fasta.gz, "
                 ".fna, .fna.gz, .fa, .fa.gz, .fastq, .fastq.gz, .fq, "
                 ".fq.gz)!\n",
                 sequences_path.c_str());
  }
  if (!sniff_overlap_format(overlaps_path, &ofmt)) {
    rt::fail("[racon_tpu::createPolisher] error: file %s has unsupported "
                 "format extension (valid extensions: .mhap, .mhap.gz, .paf, "
                 ".paf.gz, .sam, .sam.gz)!\n",
                 overlaps_path.c_str());
  }
  if (!sniff_sequence_format(target_path, &tfmt)) {
    rt::fail("[racon_tpu::createPolisher] error: file %s has unsupported "
                 "format extension (valid extensions: .fasta, .fasta.gz, "
                 ".fna, .fna.gz, .fa, .fa.gz, .fastq, .fastq.gz, .fq, "
                 ".fq.gz)!\n",
                 target_path.c_str());
  }

  sparser_.reset(new SequenceParser(sequences_path, sfmt));
  tparser_.reset(new SequenceParser(target_path, tfmt));
  oparser_.reset(new OverlapParser(overlaps_path, ofmt));

  dummy_quality_.assign(params_.window_length, '!');
  pool_.reset(new ThreadPool(params_.num_threads));
  // One aligner per worker plus one for non-pool callers
  // (ThreadPool::this_thread_index maps them to slot n).
  for (uint32_t i = 0; i < pool_->num_threads() + 1; ++i) {
    aligners_.emplace_back(
        new PoaAligner(params_.match, params_.mismatch, params_.gap));
  }
}

int64_t Pipeline::steady_now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

void Pipeline::begin_marks() {
  if (!marks_held_) {
    marks_.clear();
  }
}

void Pipeline::mark(Stage stage, int64_t* t0_ns, uint64_t items,
                    uint64_t bytes) {
  const int64_t now = steady_now_ns();
  marks_.push_back({stage, *t0_ns, now, items, bytes});
  *t0_ns = now;
}

void Pipeline::remove_invalid_overlaps(
    std::vector<std::unique_ptr<Overlap>>& overlaps, uint64_t begin,
    uint64_t end) {
  // Parity: src/polisher.cpp:285-309 — error threshold, self overlap, and
  // (kC) keep only the longest overlap per query group.
  for (uint64_t i = begin; i < end; ++i) {
    if (overlaps[i] == nullptr) {
      continue;
    }
    if (overlaps[i]->error > params_.error_threshold ||
        overlaps[i]->q_id == overlaps[i]->t_id) {
      overlaps_dropped_error_ +=
          overlaps[i]->error > params_.error_threshold ? 1 : 0;
      overlaps[i].reset();
      continue;
    }
    if (params_.type == 0) {  // kC
      for (uint64_t j = i + 1; j < end; ++j) {
        if (overlaps[j] == nullptr) {
          continue;
        }
        if (overlaps[i]->length >= overlaps[j]->length) {
          overlaps[j].reset();
        } else {
          overlaps[i].reset();
          break;
        }
      }
    }
  }
}

void Pipeline::prepare() {
  if (!windows_.empty() || !sequences_.empty()) {
    // Benign (parity: src/polisher.cpp:192-196): repeat initialization is a
    // warning, not an error.
    std::fprintf(stderr,
                 "[racon_tpu::Pipeline::prepare] warning: already "
                 "initialized!\n");
    return;
  }
  begin_marks();
  int64_t stage_t0 = steady_now_ns();

  // Targets, all at once (parity: src/polisher.cpp:200-208).
  sequences_ = tparser_->parse(0);
  targets_size_ = sequences_.size();
  if (targets_size_ == 0) {
    rt::fail(
        "[racon_tpu::Pipeline::initialize] error: empty target "
        "sequences set!\n");
  }

  std::unordered_map<std::string, uint64_t> name_to_id;
  std::unordered_map<uint64_t, uint64_t> id_to_id;
  for (uint64_t i = 0; i < targets_size_; ++i) {
    name_to_id[sequences_[i]->name + "t"] = i;
    id_to_id[i << 1 | 1] = i;
  }
  uint64_t targets_length = 0;
  for (uint64_t i = 0; i < targets_size_; ++i) {
    targets_length += sequences_[i]->data.size();
  }
  mark(Stage::kPrepareTargets, &stage_t0, targets_size_, targets_length);

  logger_.log("[racon_tpu::Pipeline::initialize] loaded target sequences");
  std::vector<bool> has_name(targets_size_, true);
  std::vector<bool> has_data(targets_size_, true);
  std::vector<bool> has_reverse_data(targets_size_, false);

  // Reads, chunked; reads that duplicate a target share its slot
  // (parity: src/polisher.cpp:226-265).
  uint64_t read_ordinal = 0, total_reads_length = 0;
  while (true) {
    auto reads = sparser_->parse(kChunkSize);
    if (reads.empty()) {
      break;
    }
    for (auto& read : reads) {
      total_reads_length += read->data.size();
      auto it = name_to_id.find(read->name + "t");
      if (it != name_to_id.end()) {
        if (read->data.size() != sequences_[it->second]->data.size() ||
            read->quality.size() != sequences_[it->second]->quality.size()) {
          rt::fail("[racon_tpu::Pipeline::initialize] error: duplicate "
                       "sequence %s with unequal data\n",
                       read->name.c_str());
        }
        name_to_id[read->name + "q"] = it->second;
        id_to_id[read_ordinal << 1 | 0] = it->second;
      } else {
        const uint64_t idx = sequences_.size();
        name_to_id[read->name + "q"] = idx;
        id_to_id[read_ordinal << 1 | 0] = idx;
        sequences_.push_back(std::move(read));
      }
      ++read_ordinal;
    }
  }
  if (read_ordinal == 0) {
    rt::fail("[racon_tpu::Pipeline::initialize] error: empty sequences "
                 "set!\n");
  }

  has_name.resize(sequences_.size(), false);
  has_data.resize(sequences_.size(), false);
  has_reverse_data.resize(sequences_.size(), false);
  mark(Stage::kPrepareReads, &stage_t0, read_ordinal, total_reads_length);

  logger_.log("[racon_tpu::Pipeline::initialize] loaded sequences");
  // Short reads get NGS windows (no trim), long reads TGS
  // (parity: src/polisher.cpp:277-278).
  window_type_ = static_cast<double>(total_reads_length) / read_ordinal <= 1000
                     ? WindowType::kNGS
                     : WindowType::kTGS;

  // Overlaps, chunked, with sequential per-query grouping
  // (parity: src/polisher.cpp:311-351).
  uint64_t group_begin = 0;
  while (true) {
    auto chunk = oparser_->parse(kChunkSize);
    if (chunk.empty()) {
      break;
    }
    overlaps_parsed_ += chunk.size();
    for (auto& o : chunk) {
      o->transmute(sequences_, name_to_id, id_to_id);
      if (!o->is_valid) {
        continue;
      }
      // New query group boundary?
      if (!overlaps_.empty() && group_begin < overlaps_.size()) {
        // find first non-null in current group
        while (group_begin < overlaps_.size() &&
               overlaps_[group_begin] == nullptr) {
          ++group_begin;
        }
        if (group_begin < overlaps_.size() &&
            overlaps_[group_begin]->q_id != o->q_id) {
          remove_invalid_overlaps(overlaps_, group_begin, overlaps_.size());
          group_begin = overlaps_.size();
        }
      }
      overlaps_.push_back(std::move(o));
    }
  }
  remove_invalid_overlaps(overlaps_, group_begin, overlaps_.size());

  // Compact.
  {
    std::vector<std::unique_ptr<Overlap>> kept;
    kept.reserve(overlaps_.size());
    for (auto& o : overlaps_) {
      if (o != nullptr) {
        kept.push_back(std::move(o));
      }
    }
    overlaps_.swap(kept);
  }
  overlaps_kept_ = overlaps_.size();

  if (overlaps_.empty()) {
    rt::fail("[racon_tpu::Pipeline::initialize] error: empty overlap "
                 "set!\n");
  }

  for (const auto& o : overlaps_) {
    if (o->strand) {
      has_reverse_data[o->q_id] = true;
    } else {
      has_data[o->q_id] = true;
    }
  }
  mark(Stage::kPrepareOverlaps, &stage_t0, overlaps_parsed_, overlaps_kept_);

  // Per-sequence transmute (free unused fields, build reverse complements)
  // on the pool (parity: src/polisher.cpp:373-382). Blocked: an item is
  // well under a microsecond for a short read, less than a task costs to
  // enqueue (align_jobs_cpu and consensus_cpu keep a task an item: theirs
  // are milliseconds each and the progress bar reads them in order).
  const uint32_t transmute_tasks = pool_->parallel_for(
      sequences_.size(), [this, &has_name, &has_data,
                          &has_reverse_data](uint64_t i) {
        sequences_[i]->transmute(has_name[i] || i < targets_size_,
                                 has_data[i] || i < targets_size_,
                                 has_reverse_data[i]);
      });
  mark(Stage::kPrepareTransmute, &stage_t0, sequences_.size(),
       transmute_tasks);

  logger_.log("[racon_tpu::Pipeline::initialize] loaded overlaps");
  // Collect alignment jobs (overlaps without a CIGAR).
  for (size_t i = 0; i < overlaps_.size(); ++i) {
    if (overlaps_[i]->cigar.empty()) {
      align_jobs_.push_back(i);
    }
  }
}

void Pipeline::align_job_views(size_t job, const char** q, uint32_t* q_len,
                               const char** t, uint32_t* t_len) const {
  overlaps_[align_jobs_[job]]->alignment_views(sequences_, q, q_len, t, t_len);
}

void Pipeline::set_job_cigar(size_t job, std::string cigar) {
  overlaps_[align_jobs_[job]]->cigar = std::move(cigar);
}

void Pipeline::align_jobs_cpu() {
  std::vector<std::future<void>> futs;
  for (size_t job : align_jobs_) {
    Overlap* o = overlaps_[job].get();
    if (!o->cigar.empty()) {
      continue;  // device already served this one
    }
    futs.emplace_back(pool_->submit([this, o] {
      const char *q, *t;
      uint32_t q_len, t_len;
      o->alignment_views(sequences_, &q, &q_len, &t, &t_len);
      o->cigar = align_global_cigar(q, q_len, t, t_len);
    }));
  }
  // 20-bin progress bar over alignment jobs
  // (parity: src/polisher.cpp:476-487).
  const size_t step = futs.size() / 20;
  for (size_t i = 0; i < futs.size(); ++i) {
    futs[i].get();
    if (step != 0 && (i + 1) % step == 0 && (i + 1) / step < 20) {
      logger_.bar("[racon_tpu::Pipeline::initialize] aligning overlaps");
    }
  }
  if (step != 0) {
    logger_.bar("[racon_tpu::Pipeline::initialize] aligning overlaps");
  } else if (!futs.empty()) {
    logger_.log("[racon_tpu::Pipeline::initialize] aligned overlaps");
  }
}

void Pipeline::build_windows() {
  begin_marks();
  int64_t stage_t0 = steady_now_ns();
  const uint64_t num_overlaps = overlaps_.size();

  // Breaking-point walks on the pool (cheap CIGAR scans now that every
  // overlap has a CIGAR; parity: src/polisher.cpp:466-488). Blocked like
  // the transmute loop and for the same reason: a 150 bp CIGAR scan costs
  // less than a task (align_jobs_cpu and consensus_cpu keep one an item).
  const uint32_t breaks_tasks =
      pool_->parallel_for(num_overlaps, [this](uint64_t i) {
        overlaps_[i]->find_breaking_points(sequences_, params_.window_length);
      });
  mark(Stage::kWindowsBreaks, &stage_t0, num_overlaps, breaks_tasks);

  // Create windows per target (parity: src/polisher.cpp:388-403).
  std::vector<uint64_t> id_to_first_window_id(targets_size_ + 1, 0);
  for (uint64_t i = 0; i < targets_size_; ++i) {
    uint32_t k = 0;
    const auto& target = *sequences_[i];
    const uint32_t t_size = static_cast<uint32_t>(target.data.size());
    for (uint32_t j = 0; j < t_size; j += params_.window_length, ++k) {
      const uint32_t length = std::min(j + params_.window_length, t_size) - j;
      windows_.push_back(createWindow(
          i, k, window_type_, target.data.data() + j, length,
          target.quality.empty() ? dummy_quality_.data()
                                 : target.quality.data() + j,
          length));
    }
    id_to_first_window_id[i + 1] = id_to_first_window_id[i] + k;
  }

  targets_coverages_.assign(targets_size_, 0);
  mark(Stage::kWindowsCreate, &stage_t0, windows_.size());

  // Distribute overlap pieces into windows (parity: src/polisher.cpp:407-461).
  for (auto& o : overlaps_) {
    ++targets_coverages_[o->t_id];
    const auto& sequence = sequences_[o->q_id];
    const auto& bp = o->breaking_points;

    for (size_t j = 0; j + 1 < bp.size(); j += 2) {
      ++layers_offered_;
      if (bp[j + 1].second - bp[j].second <
          0.02 * params_.window_length) {
        ++layers_dropped_short_;
        continue;
      }

      if (!sequence->quality.empty() || !sequence->reverse_quality.empty()) {
        const auto& quality =
            o->strand ? sequence->reverse_quality : sequence->quality;
        double average_quality = 0;
        for (uint32_t k = bp[j].second; k < bp[j + 1].second; ++k) {
          average_quality += static_cast<uint32_t>(quality[k]) - 33;
        }
        average_quality /= bp[j + 1].second - bp[j].second;
        if (average_quality < params_.quality_threshold) {
          ++layers_dropped_quality_;
          continue;
        }
      }

      const uint64_t window_id =
          id_to_first_window_id[o->t_id] + bp[j].first / params_.window_length;
      const uint32_t window_start =
          (bp[j].first / params_.window_length) * params_.window_length;

      const char* data = o->strand
                             ? sequence->reverse_complement.data() + bp[j].second
                             : sequence->data.data() + bp[j].second;
      const uint32_t data_length = bp[j + 1].second - bp[j].second;

      const char* quality =
          o->strand ? (sequence->reverse_quality.empty()
                           ? nullptr
                           : sequence->reverse_quality.data() + bp[j].second)
                    : (sequence->quality.empty()
                           ? nullptr
                           : sequence->quality.data() + bp[j].second);
      const uint32_t quality_length = quality == nullptr ? 0 : data_length;

      windows_[window_id]->add_layer(data, data_length, quality,
                                     quality_length,
                                     bp[j].first - window_start,
                                     bp[j + 1].first - window_start - 1,
                                     o->breaking_strays[j / 2]);
    }
    o.reset();
  }
  overlaps_.clear();
  align_jobs_.clear();

  done_.assign(windows_.size(), 0);
  polished_.assign(windows_.size(), 0);
  uint64_t layers = 0;  // a window's first sequence is its backbone
  for (const auto& w : windows_) {
    layers += w->sequences.size() - 1;
  }
  mark(Stage::kWindowsLayers, &stage_t0, layers);

  logger_.log("[racon_tpu::Pipeline::initialize] transformed data into "
              "windows");
}

void Pipeline::initialize() {
  begin_marks();
  marks_held_ = true;
  try {
    prepare();
    int64_t stage_t0 = steady_now_ns();
    const uint64_t jobs = align_jobs_.size();
    align_jobs_cpu();
    mark(Stage::kInitializeAlign, &stage_t0, jobs);
    build_windows();
  } catch (...) {
    marks_held_ = false;
    throw;
  }
  marks_held_ = false;
}

bool Pipeline::consensus_cpu_one(size_t i) {
  const bool polished = windows_[i]->generate_consensus(
      *aligners_[pool_->this_thread_index()], params_.trim);
  done_[i] = 1;
  polished_[i] = polished ? 1 : 0;
  return polished;
}

void Pipeline::consensus_cpu_all() {
  std::vector<std::future<void>> futs;
  for (size_t i = 0; i < windows_.size(); ++i) {
    if (done_[i]) {
      continue;
    }
    futs.emplace_back(pool_->submit([this, i] { consensus_cpu_one(i); }));
  }
  const size_t step = futs.size() / 20;
  for (size_t i = 0; i < futs.size(); ++i) {
    futs[i].get();
    if (step != 0 && (i + 1) % step == 0 && (i + 1) / step < 20) {
      logger_.bar("[racon_tpu::Pipeline::polish] generating consensus");
    }
  }
  if (step != 0) {
    logger_.bar("[racon_tpu::Pipeline::polish] generating consensus");
  } else if (!futs.empty()) {
    logger_.log("[racon_tpu::Pipeline::polish] generated consensus");
  }
}

void Pipeline::consensus_cpu_submit(size_t i) {
  if (i >= windows_.size()) {
    rt::fail("[racon_tpu::Pipeline::consensus_cpu_submit] error: window %zu "
             "out of range!\n", i);
  }
  submitted_.emplace_back(pool_->submit([this, i] { consensus_cpu_one(i); }));
}

size_t Pipeline::consensus_cpu_join() {
  std::vector<std::future<void>> futs;
  futs.swap(submitted_);
  size_t finished = 0;
  for (auto& f : futs) {
    finished += f.wait_for(std::chrono::seconds(0)) ==
                std::future_status::ready;
  }
  std::exception_ptr first;
  for (auto& f : futs) {
    try {
      f.get();
    } catch (...) {
      if (!first) {
        first = std::current_exception();
      }
    }
  }
  if (first) {
    std::rethrow_exception(first);
  }
  return finished;
}

void Pipeline::set_consensus(size_t i, std::string consensus, bool polished) {
  windows_[i]->consensus = std::move(consensus);
  done_[i] = 1;
  polished_[i] = polished ? 1 : 0;
}

void Pipeline::stitch(bool drop_unpolished_sequences,
                      std::vector<std::pair<std::string, std::string>>* dst) {
  if (stitched_) {
    rt::fail("[racon_tpu::Pipeline::stitch] error: windows already "
                 "consumed by a previous stitch!\n");
  }
  stitched_ = true;
  begin_marks();
  int64_t stage_t0 = steady_now_ns();
  const size_t first_record = dst->size();

  std::string polished_data;
  uint32_t num_polished_windows = 0;

  for (size_t i = 0; i < windows_.size(); ++i) {
    if (!done_[i]) {
      rt::fail("[racon_tpu::Pipeline::stitch] error: window %zu has no "
                   "consensus!\n",
                   i);
    }
    num_polished_windows += polished_[i] ? 1 : 0;
    polished_data += windows_[i]->consensus;

    if (i == windows_.size() - 1 || windows_[i + 1]->rank == 0) {
      const double polished_ratio =
          num_polished_windows / static_cast<double>(windows_[i]->rank + 1);

      if (!drop_unpolished_sequences || polished_ratio > 0) {
        std::string tags = params_.type == 1 ? "r" : "";
        tags += " LN:i:" + std::to_string(polished_data.size());
        tags += " RC:i:" + std::to_string(targets_coverages_[windows_[i]->id]);
        tags += " XC:f:" + std::to_string(polished_ratio);
        dst->emplace_back(sequences_[windows_[i]->id]->name + tags,
                          polished_data);
      }
      num_polished_windows = 0;
      polished_data.clear();
    }
    windows_[i].reset();
  }
  uint64_t stitched_length = 0;
  for (size_t i = first_record; i < dst->size(); ++i) {
    stitched_length += (*dst)[i].second.size();
  }
  mark(Stage::kStitchJoin, &stage_t0, dst->size() - first_record,
       stitched_length);
}

}  // namespace rt
