// preprocessing worker — C++ shell of the reference's preprocessing_service
// (SURVEY.md §2 checklist item 3; reference:
// services/preprocessing_service/src/main.rs), with the tensor compute
// relocated to the TPU engine process behind engine.embed.* request-reply
// (checklist item 4: the shell never touches the device).
//
// Two roles, same as the reference:
// 1. pipeline: data.raw_text.discovered → clean/split (native, textproc.hpp)
//    → engine.embed.batch → data.text.with_embeddings (main.rs:126-171);
//    plus the un-orphaned data.processed_text.tokenized publish
//    (SURVEY.md fact #3 — the reference's CHANGELOG.md:57-60 left it dead).
// 2. query embedding request-reply on tasks.embedding.for_query with typed
//    error replies even on undecodable input (main.rs:173-298).
//
// PIPELINED FEED (VERDICT r4 next-1): the reference's model — and our first
// three rounds' — was one synchronous embed hop per document, so each
// replica held exactly one doc in flight and the engine round-trip was
// paid per document. This shell now:
//   - keeps up to SYMBIONT_PREPROC_MAX_INFLIGHT embed requests in flight at
//     once (async inbox request-reply, single-threaded event loop), and
//   - COALESCES the sentences of multiple pending documents into one
//     engine.embed.batch hop (up to SYMBIONT_PREPROC_MAX_BATCH_SENTS), so
//     the hop count scales with total sentences, not documents;
//   - asks the engine for the compact base64 f32 reply encoding (~4.3 bytes
//     per float on the wire instead of ~10 digits of JSON).
// Per-document ack/publish semantics are unchanged: each doc's two publishes
// happen (and its delivery is acked) only after ITS vectors arrived; a
// failed/timed-out batch leaves every affected doc unacked for durable
// redelivery.
//
// Usage: preprocessing [SYMBIONT_BUS_URL=...] [SYMBIONT_ENGINE_TIMEOUT_MS=...]
//        [SYMBIONT_PREPROC_MAX_INFLIGHT=3] [SYMBIONT_PREPROC_MAX_BATCH_SENTS=128]

#include <deque>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "../../generated/cpp/symbiont_schema.hpp"
#include "common.hpp"
#include "textproc.hpp"

namespace {

const char* SERVICE = "preprocessing";

// A parsed document whose sentences are waiting for (or riding in) an
// embed hop. Holds the original delivery for the ack.
struct PendingDoc {
  symbus::BusMsg delivery;
  symbiont::RawTextMessage raw;
  std::string cleaned;
  std::vector<std::string> sentences;
  std::map<std::string, std::string> headers;  // child trace headers
};

// One in-flight engine.embed.batch request carrying 1..n documents.
struct InflightBatch {
  std::vector<PendingDoc> docs;
  size_t total_sentences = 0;
  uint64_t deadline_ms = 0;
};

}  // namespace

// The broker counts EVERY delivery attempt toward max_deliver, including
// ones a worker skips (dedupe of a copy it already holds, backpressure) —
// so a skipped redelivery silently burns a retry. When the NEXT redelivery
// would dead-letter the message, the worker must take it despite the skip
// conditions: duplicate work / memory beats data loss.
inline bool last_chance(const symbus::BusMsg& m, uint32_t max_deliver) {
  auto it = m.headers.find("X-Symbus-Deliveries");
  if (it == m.headers.end()) return false;  // core mode: no dead-letter
  return (uint32_t)std::atoi(it->second.c_str()) + 1 >= max_deliver;
}

inline size_t env_size_t(const char* key, long dflt, long lo) {
  long v = std::atol(symbiont::env_or(key, std::to_string(dflt)).c_str());
  return (size_t)(v < lo ? lo : v);  // clamp BEFORE the size_t cast: a
  // negative value must not wrap to 2^64 and disable the bound
}

int main() try {
  int engine_timeout_ms =
      std::atoi(symbiont::env_or("SYMBIONT_ENGINE_TIMEOUT_MS", "120000").c_str());
  size_t max_inflight = env_size_t("SYMBIONT_PREPROC_MAX_INFLIGHT", 3, 1);
  size_t max_batch_sents =
      env_size_t("SYMBIONT_PREPROC_MAX_BATCH_SENTS", 128, 1);
  uint32_t max_deliver = (uint32_t)std::atoi(
      symbiont::env_or("SYMBIONT_BUS_DURABLE_MAX_DELIVER", "5").c_str());
  // binary tensor frames (common.hpp / schema/frames.py): ask the engine
  // for frame replies and publish data.text.with_embeddings with the
  // float block attached — floats never pass through text. SYMBIONT_FRAMES
  // =0 restores the reference-era JSON wire for old downstream peers;
  // =f16 negotiates the half-width dtype from the ENGINE (frame16
  // encoding) and forwards those raw bytes — this shell never converts
  // floats, it re-slices whatever dtype the engine framed.
  uint8_t fmode = symbiont::frames_mode();
  bool use_frames = fmode != 0;

  symbus::Client bus;
  if (!symbiont::connect_with_retry(bus, SERVICE)) return 1;

  // durable mode: at-least-once consumption, ack only after both downstream
  // publishes succeed (SURVEY.md §5.3). Query request-reply stays core.
  bool durable = symbiont::maybe_setup_pipeline_stream(bus);
  uint32_t sid_raw =
      durable ? bus.durable_subscribe("pipeline", symbiont::subjects::Q_PREPROCESSING,
                                      symbiont::subjects::DATA_RAW_TEXT_DISCOVERED)
              : bus.subscribe(symbiont::subjects::DATA_RAW_TEXT_DISCOVERED,
                              symbiont::subjects::Q_PREPROCESSING);
  uint32_t sid_query = bus.subscribe(symbiont::subjects::TASKS_EMBEDDING_FOR_QUERY,
                                     symbiont::subjects::Q_PREPROCESSING);
  symbiont::logline("INFO", SERVICE, durable ? "ready (durable)" : "ready");

  std::deque<PendingDoc> ready;                       // parsed, not dispatched
  std::unordered_map<uint32_t, InflightBatch> inflight;  // by inbox sid
  // doc ids currently queued or in flight: an ack_wait redelivery of a doc
  // we already hold must not be embedded twice
  std::unordered_set<std::string> pending_ids;
  bool ready_high_water_warned = false;

  // Pop ready docs into one coalesced embed request (≥1 doc; stop before
  // exceeding max_batch_sents unless a single doc alone does) and send it
  // with a fresh inbox. Trace headers: a coalesced hop carries the FIRST
  // doc's trace (one request cannot ride n traces); per-doc publishes keep
  // their own traces.
  auto dispatch = [&]() {
    while (inflight.size() < max_inflight && !ready.empty()) {
      InflightBatch batch;
      json::Value texts = json::Value::array();
      while (!ready.empty()) {
        PendingDoc& d = ready.front();
        if (!batch.docs.empty() &&
            batch.total_sentences + d.sentences.size() > max_batch_sents)
          break;
        for (const auto& s : d.sentences) texts.push_back(json::Value(s));
        batch.total_sentences += d.sentences.size();
        batch.docs.push_back(std::move(d));
        ready.pop_front();
        if (batch.total_sentences >= max_batch_sents) break;
      }
      json::Value req = json::Value::object();
      req.set("texts", std::move(texts));
      // an old engine ignores the unknown "frame"/"frame16" encoding and
      // replies with JSON float lists — complete() accepts every reply form
      req.set("encoding",
              json::Value(!use_frames ? "b64"
                          : fmode == symbiont::FRAME_DTYPE_F16 ? "frame16"
                                                               : "frame"));
      std::string inbox = "_INBOX." + symbiont::uuid4();
      uint32_t sid = bus.subscribe(inbox);
      batch.deadline_ms = symbiont::now_ms() + (uint64_t)engine_timeout_ms;
      bus.publish(symbiont::subjects::ENGINE_EMBED_BATCH, req.dump(), inbox,
                  batch.docs.front().headers);
      inflight.emplace(sid, std::move(batch));
    }
  };

  // Distribute one reply's vectors back to its documents in order and
  // publish/ack per doc. Throws on malformed replies (docs stay unacked).
  // A frame reply is re-sliced per document as RAW BYTES (memcpy, no float
  // parse/format anywhere between the engine and the downstream consumers).
  auto complete = [&](InflightBatch& batch, const symbus::BusMsg& msg) {
    std::string json_part;
    symbiont::FrameView fv;
    bool framed = symbiont::split_frame(msg.headers, msg.data, json_part, fv);
    json::Value r = json::parse(framed ? json_part : msg.data);
    if (!r.at("error_message").is_null())
      throw std::runtime_error("engine error: " +
                               r.at("error_message").as_string());
    std::vector<std::vector<float>> vectors;
    if (framed) {
      if (fv.rows != batch.total_sentences)
        throw std::runtime_error(
            "engine frame holds " + std::to_string(fv.rows) +
            " rows for " + std::to_string(batch.total_sentences) +
            " sentences");
      if (!use_frames)  // frames toggled off: fall back to JSON publishes
        vectors = symbiont::frame_rows(fv);
    } else {
      vectors = symbiont::decode_vectors(r);
      if (vectors.size() != batch.total_sentences)
        throw std::runtime_error(
            "engine returned " + std::to_string(vectors.size()) +
            " vectors for " + std::to_string(batch.total_sentences) +
            " sentences");
    }
    std::string model_name = r.at("model_name").as_string();
    size_t off = 0;
    for (auto& d : batch.docs) {
      symbiont::TextWithEmbeddingsMessage out;
      out.original_id = d.raw.id;
      out.source_url = d.raw.source_url;
      out.model_name = model_name;
      out.timestamp_ms = symbiont::now_ms();
      bool publish_frame = framed && use_frames;
      for (size_t i = 0; i < d.sentences.size(); ++i) {
        symbiont::SentenceEmbedding se;
        se.sentence_text = d.sentences[i];
        if (!publish_frame)
          se.embedding = std::move(vectors[off + i]);
        out.embeddings_data.push_back(std::move(se));
      }
      if (publish_frame) {
        std::string body = out.to_json_string();
        size_t dim = fv.cols;
        size_t elem = fv.elem_size();  // 4 (f32) or 2 (negotiated f16)
        std::string raw(fv.payload + off * dim * elem,
                        d.sentences.size() * dim * elem);
        auto headers = d.headers;
        headers[symbiont::FRAME_HEADER] =
            symbiont::frame_header_value(body.size(), fv.dtype);
        bus.publish(symbiont::subjects::DATA_TEXT_WITH_EMBEDDINGS,
                    body + symbiont::make_frame(
                               raw, (uint32_t)d.sentences.size(),
                               (uint32_t)dim, fv.dtype),
                    "", headers);
      } else {
        bus.publish(symbiont::subjects::DATA_TEXT_WITH_EMBEDDINGS,
                    out.to_json_string(), "", d.headers);
      }
      off += d.sentences.size();
      // un-orphaned knowledge-graph feed (SURVEY.md fact #3)
      symbiont::TokenizedTextMessage tok;
      tok.original_id = d.raw.id;
      tok.source_url = d.raw.source_url;
      tok.tokens = symbiont::tokenize_words(d.cleaned);
      tok.sentences = d.sentences;
      tok.timestamp_ms = symbiont::now_ms();
      bus.publish(symbiont::subjects::DATA_PROCESSED_TEXT_TOKENIZED,
                  tok.to_json_string(), "", d.headers);
      bus.ack(d.delivery);  // both downstream publishes are on the broker
    }
  };

  auto forget = [&](const InflightBatch& batch) {
    for (const auto& d : batch.docs) pending_ids.erase(d.raw.id);
  };

  // fleet liveness: beat `_sys.heartbeat.<role>` so the process supervisor's
  // hang detector covers this shell (SYMBIONT_RUNNER_HEARTBEAT_S > 0)
  symbiont::Heartbeat hb = symbiont::heartbeat_from_env(SERVICE);

  while (bus.connected()) {
    auto msg = bus.next(1000);
    symbiont::maybe_heartbeat(bus, hb);

    // expired in-flight batches: drop (docs stay unacked → durable
    // redelivery after ack_wait; core mode loses them, same as before)
    uint64_t now = symbiont::now_ms();
    for (auto it = inflight.begin(); it != inflight.end();) {
      if (it->second.deadline_ms < now) {
        symbiont::logline("WARN", SERVICE,
                          "embed batch timed out (" +
                              std::to_string(it->second.docs.size()) +
                              " docs)");
        bus.unsubscribe(it->first);
        forget(it->second);
        it = inflight.erase(it);
      } else {
        ++it;
      }
    }
    if (!msg) {
      dispatch();  // a freed slot may have pending docs waiting
      continue;
    }

    // ------------------------------------------------ embed reply (inbox)
    if (auto it = inflight.find(msg->sid); it != inflight.end()) {
      bus.unsubscribe(msg->sid);
      InflightBatch batch = std::move(it->second);
      inflight.erase(it);
      try {
        complete(batch, *msg);
        forget(batch);
      } catch (const std::exception& e) {
        // transient (engine down / bad reply): leave unacked so the durable
        // stream redelivers after ack_wait
        symbiont::logline("WARN", SERVICE,
                          std::string("embed failed: ") + e.what(),
                          batch.docs.front().headers);
        forget(batch);
      }
      dispatch();
      continue;
    }

    // ------------------------------------------------------------ pipeline
    if (msg->sid == sid_raw) {
      // expired-deadline drop (Service._run_handler parity): dead work is
      // acked BEFORE any embed capacity is spent on it
      if (symbiont::drop_if_expired(bus, *msg, SERVICE)) continue;
      PendingDoc d;
      d.delivery = *msg;
      try {
        d.raw = symbiont::RawTextMessage::parse(msg->data);
      } catch (const std::exception& e) {
        symbiont::logline("WARN", SERVICE,
                          std::string("bad raw-text message: ") + e.what(),
                          msg->headers);
        bus.ack(*msg);  // permanent failure: redelivery cannot help
        continue;
      }
      d.cleaned = symbiont::clean_text(d.raw.raw_text);
      if (d.cleaned.empty()) {
        // empty cleaned text is an error at this stage (main.rs:33-39)
        symbiont::logline("WARN", SERVICE,
                          "cleaned text empty for id " + d.raw.id,
                          msg->headers);
        bus.ack(*msg);  // permanent: the document has no content
        continue;
      }
      if (pending_ids.count(d.raw.id) && !last_chance(*msg, max_deliver)) {
        // ack_wait redelivery of a doc still queued/in flight here:
        // embedding it again would duplicate downstream publishes; skip
        // WITHOUT ack (if our copy fails, a later redelivery re-enters
        // because the id is erased on drop). On the final attempt the
        // skip is overridden — a skipped delivery still counts toward
        // max_deliver, and duplicate work beats dead-lettering the doc.
        continue;
      }
      if (durable && ready.size() >= 256 && !last_chance(*msg, max_deliver)) {
        // backpressure: leave the delivery unacked for redelivery instead
        // of growing a queue whose tail would blow past ack_wait anyway
        if (!ready_high_water_warned) {
          ready_high_water_warned = true;
          symbiont::logline("WARN", SERVICE,
                            "ready backlog >= 256 docs; deferring to "
                            "redelivery");
        }
        continue;
      }
      d.sentences = symbiont::split_sentences(d.cleaned);
      d.headers = symbiont::child_headers(msg->headers);
      pending_ids.insert(d.raw.id);
      ready.push_back(std::move(d));
      dispatch();
      continue;
    }

    // ----------------------------------------------------- query embedding
    if (msg->sid == sid_query) {
      // an expired query gets NO reply: the edge's deadline-capped bus
      // timeout already fired, a late reply would land in a dead inbox
      if (symbiont::drop_if_expired(bus, *msg, SERVICE)) continue;
      if (msg->reply.empty()) {
        symbiont::logline("WARN", SERVICE, "query task without reply inbox",
                          msg->headers);
        continue;
      }
      symbiont::QueryEmbeddingResult result;
      try {
        auto task = symbiont::QueryForEmbeddingTask::parse(msg->data);
        result.request_id = task.request_id;
        auto headers = symbiont::child_headers(msg->headers);
        json::Value req = json::Value::object();
        json::Value texts = json::Value::array();
        texts.push_back(json::Value(task.text_to_embed));
        req.set("texts", std::move(texts));
        req.set("encoding", json::Value("b64"));
        // synchronous: the query path is one text on the latency path, and
        // pipeline replies arriving meanwhile stay queued for next()
        json::Value r = symbiont::engine_call(
            bus, symbiont::subjects::ENGINE_EMBED_BATCH, req,
            engine_timeout_ms, headers);
        auto vectors = symbiont::decode_vectors(r);
        result.embedding = vectors.at(0);
        result.model_name = r.at("model_name").as_string();
      } catch (const std::exception& e) {
        // typed error reply even on deserialize failure (main.rs:183-196)
        if (result.request_id.empty()) result.request_id = "unknown";
        result.error_message = e.what();
      }
      auto reply_headers = symbiont::child_headers(msg->headers);
      std::string body;
      auto accept = msg->headers.find(symbiont::ACCEPT_FRAME_HEADER);
      if (!result.error_message.has_value() && result.embedding &&
          accept != msg->headers.end() && accept->second == "1") {
        // negotiated reply frame (schema/frames.py wants_frame): the
        // [1, dim] f32 block rides appended to a schema-valid reply whose
        // embedding list is empty; requesters without the accept header
        // keep getting the reference float-list reply below
        std::vector<float> v = std::move(*result.embedding);
        std::string raw(reinterpret_cast<const char*>(v.data()),
                        v.size() * sizeof(float));
        result.embedding = std::vector<float>{};
        body = result.to_json_string();
        reply_headers[symbiont::FRAME_HEADER] =
            symbiont::frame_header_value(body.size());
        body += symbiont::make_frame(raw, 1, (uint32_t)v.size());
      } else {
        body = result.to_json_string();
      }
      bus.publish(msg->reply, body, "", reply_headers);
      continue;
    }
  }
  symbiont::logline("INFO", SERVICE, "bus connection closed; exiting");
  return 0;
} catch (const std::exception& e) {
  // bus drop mid-handler etc.: exit cleanly for the supervisor to
  // restart instead of std::terminate aborting with no log
  symbiont::logline("ERROR", SERVICE, std::string("fatal: ") + e.what());
  return 1;
}
