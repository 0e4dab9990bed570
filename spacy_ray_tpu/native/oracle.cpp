// Native arc-eager gold oracle: teacher-forced parser targets of one document.
//
// Role parity: the reference trains spaCy's parser, whose state machine and
// oracle are Cython (nn_parser.pyx, SURVEY.md §2.3 row "spaCy core"). Here the
// collate thread's hot path — 2n transitions a document, a feature row and a
// valid-action row at each — runs through this function instead of the
// interpreter.
//
// The semantics are pipeline/transition.py's (ParseState, _oracle_action,
// gold_oracle_python) and pipeline/nonproj.py's strict is_projective, to the
// element: that Python is the statement of what this computes, the fallback
// where this did not build, and what tests/test_native_oracle.py holds it to.
//
// Build: one library with murmur.cpp (native/__init__.py).

#include <cstdint>
#include <cstring>
#include <new>
#include <vector>

namespace {

constexpr int64_t kFeatures = 12;
constexpr int64_t kShift = 0;
constexpr int64_t kReduce = 1;
constexpr int64_t kUnattached = -2;  // ParseState.heads before an arc; -1 = ROOT

// nonproj.is_projective: heads in range, no crossing arc, no root (or any
// token whose head lies outside) strictly inside another arc's span.
bool strictly_projective(const int64_t* heads, int64_t n) {
  for (int64_t d = 0; d < n; ++d) {
    if (heads[d] < 0 || heads[d] >= n) return false;
  }
  for (int64_t d = 0; d < n; ++d) {
    const int64_t h = heads[d];
    if (h == d) continue;
    const int64_t lo = h < d ? h : d, hi = h < d ? d : h;
    for (int64_t k = lo + 1; k < hi; ++k) {
      const int64_t hk = heads[k];
      if (hk == k || hk < lo || hk > hi) return false;
    }
  }
  return true;
}

struct State {
  int64_t n;
  int64_t buffer = 0;  // index of b0; the buffer is [buffer, n)
  int64_t depth = 0;
  int64_t* stack;
  int64_t* heads;
  int64_t* lchild;  // [n, 2]: leftmost, second-leftmost child
  int64_t* rchild;  // [n, 2]: rightmost, second-rightmost child

  // over 6n cells of scratch
  State(int64_t n_words, int64_t* scratch)
      : n(n_words), stack(scratch), heads(scratch + n_words),
        lchild(scratch + 2 * n_words), rchild(scratch + 4 * n_words) {
    for (int64_t i = 0; i < n; ++i) heads[i] = kUnattached;
    for (int64_t i = 0; i < 2 * n; ++i) lchild[i] = rchild[i] = -1;
  }

  bool terminal() const { return buffer >= n && depth == 0; }
  bool has_b0() const { return buffer < n; }
  int64_t s0() const { return depth > 0 ? stack[depth - 1] : -1; }
  bool s0_has_head() const { return depth > 0 && heads[s0()] != kUnattached; }

  // ParseState.valid_mask takes one of four patterns while a step remains:
  //   0: b0, empty stack            SHIFT
  //   1: b0, s0 without a head      SHIFT, every LEFT-ARC, every RIGHT-ARC
  //   2: b0, s0 with a head         SHIFT, REDUCE, every RIGHT-ARC
  //   3: no b0, s0                  REDUCE (s0 has a head, or the dead-end escape)
  int64_t pattern() const {
    return !has_b0() ? 3 : depth == 0 ? 0 : s0_has_head() ? 2 : 1;
  }

  // ParseState._add_arc, without the label it keeps and nothing reads
  void add_arc(int64_t head, int64_t dep) {
    heads[dep] = head;
    if (head < 0) return;
    if (dep < head) {
      int64_t* l = lchild + 2 * head;
      if (l[0] == -1 || dep < l[0]) {
        l[1] = l[0];
        l[0] = dep;
      } else if (l[1] == -1 || dep < l[1]) {
        l[1] = dep;
      }
    } else {
      int64_t* r = rchild + 2 * head;
      if (r[0] == -1 || dep > r[0]) {
        r[1] = r[0];
        r[0] = dep;
      } else if (r[1] == -1 || dep > r[1]) {
        r[1] = dep;
      }
    }
  }

  // ParseState.apply of an action its pattern allows. Returns the token that
  // left the stack, or -1 where one joined it (b0, as it was before the call).
  int64_t apply(int64_t action) {
    if (action == kShift) {
      stack[depth++] = buffer++;
      return -1;
    }
    if (action == kReduce) {
      const int64_t tok = stack[--depth];
      if (heads[tok] == kUnattached) add_arc(-1, tok);  // dead-end escape
      return tok;
    }
    if ((action - 2) % 2 == 0) {  // LEFT-ARC
      const int64_t tok = stack[--depth];
      add_arc(buffer, tok);
      return tok;
    }
    add_arc(s0(), buffer);  // RIGHT-ARC
    stack[depth++] = buffer++;
    return -1;
  }

  void features(int64_t* f) const {
    for (int64_t k = 0; k < kFeatures; ++k) f[k] = -1;
    for (int64_t k = 0; k < 3 && k < depth; ++k) f[k] = stack[depth - 1 - k];
    for (int64_t k = 0; k < 3 && buffer + k < n; ++k) f[3 + k] = buffer + k;
    if (depth >= 1) {
      const int64_t top = stack[depth - 1];
      f[6] = lchild[2 * top];
      f[7] = rchild[2 * top];
      f[10] = lchild[2 * top + 1];
      f[11] = rchild[2 * top + 1];
    }
    if (depth >= 2) {
      const int64_t s1 = stack[depth - 2];
      f[8] = lchild[2 * s1];
      f[9] = rchild[2 * s1];
    }
  }
};

// the four rows State::pattern() indexes, [4, n_actions]
void fill_patterns(uint8_t* mask, int64_t n_labels) {
  const int64_t n_actions = 2 + 2 * n_labels;
  std::memset(mask, 0, static_cast<size_t>(4 * n_actions));
  mask[0 * n_actions + kShift] = 1;
  mask[1 * n_actions + kShift] = 1;
  mask[2 * n_actions + kShift] = 1;
  mask[2 * n_actions + kReduce] = 1;
  mask[3 * n_actions + kReduce] = 1;
  for (int64_t i = 0; i < n_labels; ++i) {
    mask[1 * n_actions + 2 + 2 * i] = 1;
    mask[1 * n_actions + 3 + 2 * i] = 1;
    mask[2 * n_actions + 3 + 2 * i] = 1;
  }
}

}  // namespace

extern "C" {

// heads[i] == i marks a root. Writes actions [S], feats [S, 12] and
// valid [S, 2 + 2 * n_labels] (one byte a cell, 0 or 1) for S <= cap steps
// and returns S; -1 where gold_oracle_python returns None (empty, not
// strictly projective, stuck, not terminal, replay is not the gold tree);
// -2 where this function declines and the caller runs the Python (a label
// id outside [0, n_labels), which the Python indexes with; a negative
// n_labels; no memory).
int64_t arc_eager_gold_oracle(const int64_t* heads, const int64_t* label_ids,
                              int64_t n, int64_t n_labels, int64_t* actions,
                              int64_t* feats, uint8_t* valid, int64_t cap) {
  if (n_labels < 0) return -2;
  if (n <= 0) return -1;
  if (!strictly_projective(heads, n)) return -1;
  const int64_t n_actions = 2 + 2 * n_labels;
  std::vector<int64_t> scratch;
  std::vector<uint8_t> masks;
  try {
    scratch.assign(static_cast<size_t>(10 * n), 0);
    masks.resize(static_cast<size_t>(4 * n_actions));
  } catch (const std::bad_alloc&) {
    return -2;
  }
  State st(n, scratch.data());
  int64_t* gold = scratch.data() + 6 * n;  // -1 = ROOT
  int64_t* last_dep = gold + n;  // the last token whose gold head this is
  // what the Python's two scans of the stack read off, kept as the state
  // moves: is a token on the stack, and how many tokens on the stack have
  // this token as their gold head
  int64_t* on_stack = last_dep + n;
  int64_t* waiting_for = on_stack + n;
  for (int64_t i = 0; i < n; ++i) {
    gold[i] = heads[i] == i ? -1 : heads[i];
    last_dep[i] = -1;
  }
  for (int64_t i = 0; i < n; ++i) {
    if (gold[i] >= 0) last_dep[gold[i]] = i;
  }
  fill_patterns(masks.data(), n_labels);

  int64_t steps = 0;
  while (!st.terminal() && steps < cap) {
    st.features(feats + steps * kFeatures);
    const uint8_t* row = masks.data() + st.pattern() * n_actions;
    std::memcpy(valid + steps * n_actions, row, static_cast<size_t>(n_actions));

    // _oracle_action: LEFT-ARC, RIGHT-ARC, REDUCE, SHIFT in Nivre's order
    const int64_t b0 = st.buffer, s0 = st.s0();
    int64_t action = kShift;
    if (!st.has_b0()) {
      action = kReduce;
    } else if (s0 >= 0) {
      if (gold[s0] == b0 && !st.s0_has_head()) {
        if (label_ids[s0] < 0 || label_ids[s0] >= n_labels) return -2;
        action = 2 + 2 * label_ids[s0];
      } else if (gold[b0] == s0) {
        if (label_ids[b0] < 0 || label_ids[b0] >= n_labels) return -2;
        action = 3 + 2 * label_ids[b0];
      } else if (st.s0_has_head()) {
        const bool s0_done = last_dep[s0] < b0;
        // b0's gold head, or a gold dependent of b0, lies below s0 (ROOT included)
        const bool head_below =
            gold[b0] == -1 || (on_stack[gold[b0]] && gold[b0] != s0);
        const bool dep_below = waiting_for[b0] - (gold[s0] == b0 ? 1 : 0) > 0;
        if (s0_done && (head_below || dep_below)) action = kReduce;
      }
    }
    if (!row[action]) return -1;  // oracle stuck
    actions[steps++] = action;
    const int64_t left = st.apply(action);
    const int64_t moved = left >= 0 ? left : b0;
    on_stack[moved] = left < 0;
    if (gold[moved] >= 0) waiting_for[gold[moved]] += left < 0 ? 1 : -1;
  }
  if (!st.terminal()) return -1;
  for (int64_t d = 0; d < n; ++d) {
    if (st.heads[d] != gold[d]) return -1;  // the replay is not the gold tree
  }
  return steps;
}

// The state rows of a run whose actions are known (what a memo keeps of the
// function above: its decisions): feats [steps, 12] and valid [steps,
// 2 + 2 * n_labels] before each of ``actions``, over ``n`` words. Returns
// ``steps``, or -1 where an action is not one its state allows or the run
// does not end with the machine (not this document's actions), -2 for no
// memory.
int64_t arc_eager_replay(const int32_t* actions, int64_t steps, int64_t n,
                         int64_t n_labels, int64_t* feats, uint8_t* valid) {
  if (n <= 0 || n_labels < 0 || steps != 2 * n) return -1;
  const int64_t n_actions = 2 + 2 * n_labels;
  std::vector<int64_t> scratch;
  std::vector<uint8_t> masks;
  try {
    scratch.resize(static_cast<size_t>(6 * n));
    masks.resize(static_cast<size_t>(4 * n_actions));
  } catch (const std::bad_alloc&) {
    return -2;
  }
  State st(n, scratch.data());
  fill_patterns(masks.data(), n_labels);
  for (int64_t step = 0; step < steps; ++step) {
    if (st.terminal()) return -1;
    st.features(feats + step * kFeatures);
    const uint8_t* row = masks.data() + st.pattern() * n_actions;
    std::memcpy(valid + step * n_actions, row, static_cast<size_t>(n_actions));
    const int64_t action = actions[step];
    if (action < 0 || action >= n_actions || !row[action]) return -1;
    st.apply(action);
  }
  return st.terminal() ? steps : -1;
}

}  // extern "C"
