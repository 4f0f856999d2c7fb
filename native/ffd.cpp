// Native FFD solver core — the low-latency tier of the solver stack.
//
// The TPU batch solver amortizes beautifully at 10k+ pods but a single
// dispatch costs ~ms; the steady-state reconcile loop
// mostly sees batches of 1-100 pods.  This C++ core runs those in
// microseconds with EXACTLY the same policy as solver/reference.py:
//
//   per group (caller supplies FFD order):
//     unconstrained: first-fit open slots in creation order, then two-stage
//       new nodes (bulk argmin of price/min(ppn, remaining) + re-scored tail)
//     zone/hostname constrained (spread, anti-affinity): per-pod sequential
//       loop with skew/anti zone checks and per-slot selector counters —
//       the exact oracle semantics, cheap at this batch size
//
// Provisioner limits are enforced on both paths (usage + node capacity must
// stay under the limit row).  Positive pod-affinity is NOT handled here; the
// scheduler routes those groups to the device/oracle (has_topology gate in
// solver/native.py).
//
// Build: make native   (g++ -O2 -shared -fPIC)
// ABI: plain C, consumed via ctypes (no pybind11 in the image).

#include <cstdint>
#include <cstring>
#include <limits>
#include <vector>

namespace {

constexpr float kBig = std::numeric_limits<float>::max();
constexpr int kNoSel = -1;

inline float slot_capacity(const float* res, const float* req, int R) {
  float cap = kBig;
  for (int r = 0; r < R; ++r) {
    if (req[r] > 0.0f) {
      float c = (res[r] + 1e-6f) / req[r];
      if (c < cap) cap = c;
    }
  }
  if (cap == kBig) return 0.0f;  // zero-request pod: pods resource still caps
  float f = static_cast<float>(static_cast<long long>(cap));
  return f < 0.0f ? 0.0f : f;
}

struct Ctx {
  int G, C, D, R, NE, NR, S, Z, P;
  const float* req;
  const int32_t* counts;
  const uint8_t* F;
  const uint8_t* dom_ok;
  const float* alloc;
  const float* price;
  const uint8_t* avail;
  const uint8_t* ex_ok;
  // topology
  const int32_t* g_zone_spread;  // [G] selector slot or -1
  const int32_t* g_zone_skew;    // [G]
  const int32_t* g_host_spread;  // [G]
  const int32_t* g_host_cap;     // [G] (0 = anti-affinity non-matcher block)
  const int32_t* g_zone_anti;    // [G]
  const uint8_t* sel_match;      // [S,G]
  const int32_t* dom_zone;       // [D]
  // provisioner limits
  const int32_t* cand_prov;      // [C]
  const float* cand_cap;         // [C,R]
  const float* prov_limits;      // [P,R]
  // state
  float* slot_res;               // [NR,R]
  int32_t* slot_cand;            // [NR]
  int32_t* slot_dom;             // [NR]
  float* slot_price;             // [NR]
  int32_t* slot_zone;            // [NR]
  int32_t* selcnt;               // [NR,S] selector-matching pods per slot
  int32_t* zc;                   // [S,Z]
  float* prov_used;              // [P,R]
  int32_t* takes;                // [G,NR]
  int n_used;
};

inline bool slot_compat(const Ctx& x, int g, int s) {
  if (x.slot_cand[s] >= 0) {
    int c = x.slot_cand[s], d = x.slot_dom[s];
    return x.F[(size_t)g * x.C + c] && x.avail[(size_t)c * x.D + d] &&
           x.dom_ok[(size_t)g * x.D + d];
  }
  return s < x.NE && x.ex_ok[(size_t)g * x.NE + s];
}

inline bool limit_ok(const Ctx& x, int c) {
  int p = x.cand_prov[c];
  for (int r = 0; r < x.R; ++r) {
    if (x.prov_used[(size_t)p * x.R + r] + x.cand_cap[(size_t)c * x.R + r] >
        x.prov_limits[(size_t)p * x.R + r] + 1e-6f)
      return false;
  }
  return true;
}

inline void charge_limit(Ctx& x, int c) {
  int p = x.cand_prov[c];
  for (int r = 0; r < x.R; ++r)
    x.prov_used[(size_t)p * x.R + r] += x.cand_cap[(size_t)c * x.R + r];
}

// max additional group-g pods this slot takes under hostname rules
inline float host_headroom(const Ctx& x, int g, int s) {
  int sh = x.g_host_spread[g];
  if (sh < 0) return kBig;
  int have = x.selcnt[(size_t)s * x.S + sh];
  int hk = x.g_host_cap[g];
  if (hk > 0) {
    float hr = (float)(hk - have);
    return hr < 0.0f ? 0.0f : hr;
  }
  // anti-affinity non-matcher: blocked only where matchers already sit
  return have > 0 ? 0.0f : kBig;
}

// is zone z allowed for one more group-g pod right now?
bool zone_allowed(const Ctx& x, int g, int z, const std::vector<uint8_t>& el) {
  int zsp = x.g_zone_spread[g];
  if (zsp >= 0) {
    int min_c = INT32_MAX;
    for (int q = 0; q < x.Z; ++q)
      if (el[q] && x.zc[(size_t)zsp * x.Z + q] < min_c)
        min_c = x.zc[(size_t)zsp * x.Z + q];
    if (min_c == INT32_MAX) min_c = 0;
    if (x.zc[(size_t)zsp * x.Z + z] + 1 - min_c > x.g_zone_skew[g]) return false;
  }
  int za = x.g_zone_anti[g];
  if (za >= 0) {
    int have = x.zc[(size_t)za * x.Z + z];
    bool self = x.sel_match[(size_t)za * x.G + g];
    if (self ? have >= 1 : have > 0) return false;
  }
  return true;
}

void observe(Ctx& x, int g, int s, int z, int n) {
  for (int q = 0; q < x.S; ++q) {
    if (x.sel_match[(size_t)q * x.G + g]) {
      x.selcnt[(size_t)s * x.S + q] += n;
      x.zc[(size_t)q * x.Z + z] += n;
    }
  }
}

// best new-node (c, d): argmin price/min(ppn, remaining).  Ties at exactly
// equal $/pod break toward the LARGER fully-fillable candidate (ppn <=
// remaining: the group's own remainder fills it, so the $ outcome is
// identical by construction and the cluster gets fewer, larger nodes —
// mirrors solver/tpu.py's size tie-break), then lower price, then candidate
// idx, domain idx.  zone_filter < 0 = any.
bool best_new(const Ctx& x, int g, int remaining, int zone_filter,
              const std::vector<uint8_t>* zone_el,
              int* out_c, int* out_d, float* out_ppn, float* out_price,
              int nz_el = 1) {
  const float* rg = x.req + (size_t)g * x.R;
  float best_score = kBig, best_price = kBig, best_full = -1.0f;
  int best_c = -1, best_d = -1;
  float best_ppn = 0.0f;
  // candidate-invariant pieces of the size tie-break, hoisted:
  // hostname cap on a fresh node, and the per-zone share for spread groups.
  // nz_el is the count of the group's ELIGIBLE zones (passed by the caller,
  // which already built the set) — not the zones allowed at this instant:
  // after round one a skew-gated spread admits zones one at a time, and
  // dividing by that transient 1 would re-admit the oversized purchase the
  // guard exists to prevent.  The sequential interleave makes the true
  // per-node fill uncertain (skew gating shifts zone shares as counts
  // move), so demand TWO full nodes' worth of share before betting on the
  // bigger type — large fleet groups (share >> ppn) keep the tie-break,
  // adversarial small spreads fall back to the oracle's price tie.
  const int sh_g = x.g_host_spread[g];
  const int hk_g = x.g_host_cap[g];
  float guard_rem = (float)remaining;
  if (x.g_zone_spread[g] >= 0 && nz_el > 1)
    guard_rem = (float)(remaining / nz_el) * 0.5f;
  for (int c = 0; c < x.C; ++c) {
    if (!x.F[(size_t)g * x.C + c]) continue;
    if (!limit_ok(x, c)) continue;
    float ppn = slot_capacity(x.alloc + (size_t)c * x.R, rg, x.R);
    if (ppn < 1.0f) continue;
    float denom = ppn < (float)remaining ? ppn : (float)remaining;
    if (denom < 1.0f) denom = 1.0f;
    // effective take on a FRESH node includes the hostname cap (an
    // anti-affine group takes 1 pod per node regardless of resources) —
    // without it the size tie-break would buy big nodes it can never fill
    float take_new = ppn;
    if (sh_g >= 0 && hk_g > 0 && (float)hk_g < take_new)
      take_new = (float)hk_g;
    float full = take_new <= guard_rem ? take_new : 0.0f;
    for (int d = 0; d < x.D; ++d) {
      if (!x.avail[(size_t)c * x.D + d] || !x.dom_ok[(size_t)g * x.D + d])
        continue;
      int z = x.dom_zone[d];
      if (zone_filter >= 0 && z != zone_filter) continue;
      if (zone_el && !(*zone_el)[z]) continue;
      float p = x.price[(size_t)c * x.D + d];
      float score = p / denom;
      if (score < best_score ||
          (score == best_score &&
           (full > best_full ||
            (full == best_full && p < best_price)))) {
        best_score = score;
        best_price = p;
        best_full = full;
        best_c = c;
        best_d = d;
        best_ppn = ppn;
      }
    }
  }
  if (best_c < 0) return false;
  *out_c = best_c;
  *out_d = best_d;
  *out_ppn = best_ppn;
  *out_price = best_price;
  return true;
}

int open_node(Ctx& x, int g, int c, int d, float price) {
  if (x.n_used >= x.NR) return -1;
  int s = x.n_used++;
  x.slot_cand[s] = c;
  x.slot_dom[s] = d;
  x.slot_price[s] = price;
  x.slot_zone[s] = x.dom_zone[d];
  std::memcpy(x.slot_res + (size_t)s * x.R, x.alloc + (size_t)c * x.R,
              sizeof(float) * x.R);
  charge_limit(x, c);
  return s;
}

void place(Ctx& x, int g, int s, int n) {
  const float* rg = x.req + (size_t)g * x.R;
  x.takes[(size_t)g * x.NR + s] += n;
  float* res = x.slot_res + (size_t)s * x.R;
  for (int r = 0; r < x.R; ++r) res[r] -= n * rg[r];
  observe(x, g, s, x.slot_zone[s], n);
}

// sequential per-pod loop for zone/hostname-constrained groups (the oracle's
// _place_group semantics; cheap at native-tier batch sizes)
int place_constrained(Ctx& x, int g) {
  const float* rg = x.req + (size_t)g * x.R;
  int remaining = x.counts[g];
  // zones this group's requirements admit at all
  std::vector<uint8_t> el(x.Z, 0);
  for (int d = 0; d < x.D; ++d)
    if (x.dom_ok[(size_t)g * x.D + d]) el[x.dom_zone[d]] = 1;
  int nz_el = 0;
  for (int q = 0; q < x.Z; ++q)
    if (el[q]) ++nz_el;

  while (remaining > 0) {
    // earliest open slot in an allowed zone with capacity + host headroom
    int chosen = -1;
    for (int s = 0; s < x.n_used; ++s) {
      if (!slot_compat(x, g, s)) continue;
      int z = x.slot_zone[s];
      if (!el[z] || !zone_allowed(x, g, z, el)) continue;
      if (slot_capacity(x.slot_res + (size_t)s * x.R, rg, x.R) < 1.0f) continue;
      if (host_headroom(x, g, s) < 1.0f) continue;
      chosen = s;
      break;
    }
    if (chosen >= 0) {
      place(x, g, chosen, 1);
      --remaining;
      continue;
    }
    // new node in the cheapest allowed zone
    std::vector<uint8_t> zel(x.Z, 0);
    bool any = false;
    for (int z = 0; z < x.Z; ++z) {
      zel[z] = el[z] && zone_allowed(x, g, z, el);
      any |= (bool)zel[z];
    }
    if (!any) break;
    int c, d;
    float ppn, price;
    if (!best_new(x, g, remaining, -1, &zel, &c, &d, &ppn, &price, nz_el)) break;
    int s = open_node(x, g, c, d, price);
    if (s < 0) return remaining;  // NR exhausted
    place(x, g, s, 1);
    --remaining;
  }
  return remaining;
}

// bulk path for unconstrained groups (identical to the original fast loop,
// plus provisioner-limit enforcement)
int place_bulk(Ctx& x, int g) {
  const float* rg = x.req + (size_t)g * x.R;
  int remaining = x.counts[g];

  for (int s = 0; s < x.n_used && remaining > 0; ++s) {
    if (!slot_compat(x, g, s)) continue;
    float cap = slot_capacity(x.slot_res + (size_t)s * x.R, rg, x.R);
    if (cap < 1.0f) continue;
    int take = remaining < (int)cap ? remaining : (int)cap;
    place(x, g, s, take);
    remaining -= take;
  }

  for (int stage = 0; stage < 2 && remaining > 0; ++stage) {
    int c, d;
    float ppn, price;
    if (!best_new(x, g, remaining, -1, nullptr, &c, &d, &ppn, &price)) break;
    int per = (int)ppn;
    int nodes = (stage == 0) ? remaining / per : 1;
    for (int k = 0; k < nodes && remaining > 0; ++k) {
      // re-check the limit before every node; fall back to a fresh pick
      if (!limit_ok(x, c)) { stage = -1; break; }
      int s = open_node(x, g, c, d, price);
      if (s < 0) return remaining;
      int take = remaining < per ? remaining : per;
      place(x, g, s, take);
      remaining -= take;
    }
    if (stage == 1 && remaining > 0) stage = 0;
  }
  return remaining;
}

}  // namespace

extern "C" {

// Returns 0 on success, -1 if NR slots were exhausted (partial result valid:
// unplaced pods are in `infeasible`).
int kt_ffd_solve(
    int G, int C, int D, int R, int NE, int NR, int S, int Z, int P,
    const float* req,            // [G,R]
    const int32_t* counts,       // [G]
    const uint8_t* F,            // [G,C]
    const uint8_t* dom_ok,       // [G,D]
    const float* alloc,          // [C,R]
    const float* price,          // [C,D]
    const uint8_t* avail,        // [C,D]
    const float* ex_res,         // [NE,R]
    const uint8_t* ex_ok,        // [G,NE]
    const int32_t* ex_zone,      // [NE]
    const int32_t* ex_selcnt,    // [NE,S]
    const int32_t* g_zone_spread,// [G]
    const int32_t* g_zone_skew,  // [G]
    const int32_t* g_host_spread,// [G]
    const int32_t* g_host_cap,   // [G]
    const int32_t* g_zone_anti,  // [G]
    const uint8_t* sel_match,    // [S,G]
    const int32_t* dom_zone,     // [D]
    const int32_t* zc0,          // [S,Z]
    const int32_t* cand_prov,    // [C]
    const float* cand_cap,       // [C,R]
    const float* prov_limits,    // [P,R]
    const float* prov_used0,     // [P,R]
    float* slot_res,             // [NR,R] scratch+output residuals
    int32_t* slot_cand,          // [NR] out (-1 = existing)
    int32_t* slot_dom,           // [NR] out
    float* slot_price,           // [NR] out
    int32_t* takes,              // [G,NR] out
    int32_t* n_used_out,         // out
    int32_t* infeasible)         // [G] out
{
  Ctx x;
  x.G = G; x.C = C; x.D = D; x.R = R; x.NE = NE; x.NR = NR;
  x.S = S; x.Z = Z; x.P = P;
  x.req = req; x.counts = counts; x.F = F; x.dom_ok = dom_ok;
  x.alloc = alloc; x.price = price; x.avail = avail; x.ex_ok = ex_ok;
  x.g_zone_spread = g_zone_spread; x.g_zone_skew = g_zone_skew;
  x.g_host_spread = g_host_spread; x.g_host_cap = g_host_cap;
  x.g_zone_anti = g_zone_anti; x.sel_match = sel_match; x.dom_zone = dom_zone;
  x.cand_prov = cand_prov; x.cand_cap = cand_cap; x.prov_limits = prov_limits;
  x.slot_res = slot_res; x.slot_cand = slot_cand; x.slot_dom = slot_dom;
  x.slot_price = slot_price; x.takes = takes;

  std::vector<int32_t> slot_zone(NR, 0);
  std::vector<int32_t> selcnt((size_t)NR * S, 0);
  std::vector<int32_t> zc((size_t)S * Z, 0);
  std::vector<float> prov_used((size_t)P * R, 0.0f);
  x.slot_zone = slot_zone.data();
  x.selcnt = selcnt.data();
  x.zc = zc.data();
  x.prov_used = prov_used.data();

  for (int s = 0; s < NR; ++s) {
    slot_cand[s] = -1;
    slot_dom[s] = -1;
    slot_price[s] = 0.0f;
  }
  for (int s = 0; s < NE; ++s) {
    std::memcpy(slot_res + (size_t)s * R, ex_res + (size_t)s * R,
                sizeof(float) * R);
    slot_zone[s] = ex_zone[s];
    std::memcpy(selcnt.data() + (size_t)s * S, ex_selcnt + (size_t)s * S,
                sizeof(int32_t) * S);
  }
  std::memcpy(zc.data(), zc0, sizeof(int32_t) * (size_t)S * Z);
  std::memcpy(prov_used.data(), prov_used0, sizeof(float) * (size_t)P * R);
  std::memset(takes, 0, sizeof(int32_t) * (size_t)G * NR);
  std::memset(infeasible, 0, sizeof(int32_t) * G);

  x.n_used = NE;
  int rc = 0;

  for (int g = 0; g < G; ++g) {
    if (counts[g] <= 0) continue;
    bool constrained = g_zone_spread[g] != kNoSel ||
                       g_host_spread[g] != kNoSel ||
                       g_zone_anti[g] != kNoSel;
    int remaining = constrained ? place_constrained(x, g) : place_bulk(x, g);
    infeasible[g] = remaining;
    if (x.n_used >= NR && remaining > 0) rc = -1;
  }

  *n_used_out = x.n_used;
  return rc;
}

const char* kt_version() { return "karpenter-tpu-native 0.2.0"; }

}  // extern "C"
