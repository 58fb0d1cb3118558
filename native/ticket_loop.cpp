// Batch deli ticket loop — the host sequencing hot path in C++.
//
// Reference: deli's ticket() state machine
// (server/routerlicious/packages/lambdas/src/deli/lambda.ts:742-1150):
// per-document, per-op — duplicate/gap detection on clientSequenceNumber,
// stale-refSeq rejection, sequence-number assignment, per-client refSeq
// update and MSN recomputation (min over per-client refSeqs,
// lambda.ts:929-938). The Python DocumentSequencer (service/sequencer.py)
// carries the full semantics (joins, leaves, nacks, scopes, control
// messages, traces); this library executes the steady-state write-client
// fast path for whole fleets in one call.
//
// Layout (all int32, C-contiguous):
//   doc_state  [n_docs, 2]               : {seq, min_seq}
//   clients    [n_docs, max_writers, 3]  : {active, client_seq, ref_seq}
//   ops        [n_docs, k, 3]            : {client, cseq, ref}
//   out        [n_docs, k, 2]            : {assigned seq (0 = dup-dropped),
//                                           msn}
//   err        [n_docs]                  : first error code (0 = clean;
//                                          1 gap, 2 stale ref, 3 unknown
//                                          client) — an erred doc stops
//                                          ticketing so the caller can
//                                          replay it through the Python
//                                          slow path (nacks etc.).
//
// The MSN is maintained incrementally: a per-doc running minimum is only
// recomputed when the op moves the current minimum holder.

#include <cstdint>

extern "C" {

int32_t ticket_batch(int64_t n_docs, int64_t k, int64_t max_writers,
                     int32_t *doc_state, int32_t *clients,
                     const int32_t *ops, int32_t *out, int32_t *err) {
  int32_t bad_docs = 0;
  for (int64_t d = 0; d < n_docs; ++d) {
    int32_t seq = doc_state[d * 2];
    const int32_t min_floor = doc_state[d * 2 + 1];
    int32_t *cl = clients + d * max_writers * 3;
    const int32_t *op = ops + d * k * 3;
    int32_t *o = out + d * k * 2;
    err[d] = 0;

    // Current MSN: min refSeq over active clients (empty -> seq).
    auto compute_msn = [&]() {
      int64_t m = -1;
      for (int64_t c = 0; c < max_writers; ++c) {
        if (cl[c * 3]) {
          int32_t r = cl[c * 3 + 2];
          if (m < 0 || r < m) m = r;
        }
      }
      return m < 0 ? seq : (int32_t)m;
    };
    int32_t msn = compute_msn();
    if (msn < min_floor) msn = min_floor;

    for (int64_t i = 0; i < k; ++i) {
      const int32_t client = op[i * 3];
      const int32_t cseq = op[i * 3 + 1];
      const int32_t ref = op[i * 3 + 2];
      if (client < 0 || client >= max_writers || !cl[client * 3]) {
        err[d] = 3;
        break;
      }
      int32_t *entry = cl + client * 3;
      if (cseq <= entry[1]) {  // duplicate: dropped, no seq consumed
        o[i * 2] = 0;
        o[i * 2 + 1] = msn;
        continue;
      }
      if (cseq != entry[1] + 1) {  // gap -> caller nacks via slow path
        err[d] = 1;
        break;
      }
      if (ref < msn) {  // stale reference below the collab floor
        err[d] = 2;
        break;
      }
      entry[1] = cseq;
      const int32_t old_ref = entry[2];
      entry[2] = ref;
      seq += 1;
      if (ref < msn) {
        msn = ref;  // unreachable (checked above); kept for clarity
      } else if (old_ref == msn && ref > msn) {
        msn = compute_msn();  // the minimum holder moved up
      }
      o[i * 2] = seq;
      o[i * 2 + 1] = msn;
    }
    doc_state[d * 2] = seq;
    doc_state[d * 2 + 1] = msn;
    if (err[d]) ++bad_docs;
  }
  return bad_docs;
}

}  // extern "C"
