// Native apply — one group's apply transaction as ONE call into SQLite.
//
// models/sqlite_sm.py applies a drained run's batch for a group as one
// transaction: BEGIN; per command SAVEPOINT _apply, the command, RELEASE
// _apply; the _raft_meta upsert in resume mode; COMMIT.  Through
// CPython's sqlite3 module that is 2 + 3n calls, and the module gives
// the interpreter up around every prepare, step and reset inside each;
// in a served engine of ~30 threads every hand-over that meets a holder
// waits about a millisecond to get the interpreter back.  apply_txn()
// runs the same transaction on the SAME handle (the sqlite3* of the
// state machine's own sqlite3.Connection, borrowed and verified by
// sqlite_sm.py); reached through ctypes.CDLL, the interpreter is given
// up once, for the whole call.
//
// It commits the plain case and nothing else.  Whatever is not a
// data-changing statement that runs straight to SQLITE_DONE -- an
// error of any kind, a second statement in a command, a statement that
// returns rows or only controls the transaction (COMMIT, ROLLBACK,
// SAVEPOINT: they would change the bracket this function holds), a
// statement with a parameter to bind (`?`, `:a`: the module refuses it,
// "Incorrect number of bindings supplied", where a bare step would run
// it with NULLs) -- ends in ROLLBACK and a non-zero return, and the
// caller runs the SAME items through its Python loop, whose outcomes
// (an error's class and text, per-statement isolation, the disk-full
// branch) are thereby the only ones there are.
//
// This machine has no sqlite3.h: the prototypes below are the C API's,
// unchanged since 3.7.  Linked with -l:libsqlite3.so.0, the object
// _sqlite3 has already loaded: a handle may only be used with the
// library that made it, and apply_sqlite_id() lets the loader check
// that it is (native/build.py).
//
// apply_checkpoint_many() is a compaction round's batch the same way:
// the FULL checkpoints of many borrowed handles, several threads at a
// time, in one call, where one `PRAGMA wal_checkpoint(FULL)` a file
// through the module gives the interpreter up and takes it back for
// each file on each of a pool's threads.
//
// apply_reopen() is what a released machine's reopen asks of a fresh
// connection (its pragmas, and in resume mode the applied index on
// file) in one call, where through the module it is a prepare, step and
// reset a statement, each a wait for the interpreter.
//
// ABI: plain C, consumed via ctypes (no pybind11 in this environment).

#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstring>
#include <system_error>
#include <thread>
#include <vector>

extern "C" {

typedef struct sqlite3 sqlite3;
typedef struct sqlite3_stmt sqlite3_stmt;

int sqlite3_exec(sqlite3*, const char*,
                 int (*)(void*, int, char**, char**), void*, char**);
int sqlite3_prepare_v2(sqlite3*, const char*, int, sqlite3_stmt**,
                       const char**);
int sqlite3_step(sqlite3_stmt*);
int sqlite3_finalize(sqlite3_stmt*);
int sqlite3_stmt_readonly(sqlite3_stmt*);
int sqlite3_bind_parameter_count(sqlite3_stmt*);
int sqlite3_get_autocommit(sqlite3*);
const char* sqlite3_db_filename(sqlite3*, const char*);
const char* sqlite3_libversion(void);
int sqlite3_wal_checkpoint_v2(sqlite3*, const char*, int, int*, int*);
int sqlite3_column_type(sqlite3_stmt*, int);
long long sqlite3_column_int64(sqlite3_stmt*, int);

}  // extern "C"

namespace {

constexpr int kOk = 0;      // SQLITE_OK
constexpr int kDone = 101;  // SQLITE_DONE
constexpr int kRow = 100;   // SQLITE_ROW
constexpr int kInteger = 1;  // SQLITE_INTEGER
constexpr int kCheckpointFull = 1;  // SQLITE_CHECKPOINT_FULL

bool run(sqlite3* db, const char* sql) {
  return sqlite3_exec(db, sql, nullptr, nullptr, nullptr) == kOk;
}

// One replicated command inside its savepoint; true when it is applied.
bool apply_one(sqlite3* db, const char* sql, int len) {
  sqlite3_stmt* stmt = nullptr;
  const char* tail = nullptr;
  // len + 1: the caller's strings are NUL-terminated (ctypes c_char_p);
  // a NUL inside one is the Python loop's to refuse.
  if (std::memchr(sql, 0, len) != nullptr ||
      sqlite3_prepare_v2(db, sql, len + 1, &stmt, &tail) != kOk ||
      stmt == nullptr)
    return false;
  bool ok = !sqlite3_stmt_readonly(stmt) &&
            sqlite3_bind_parameter_count(stmt) == 0;
  for (const char* p = tail; ok && p < sql + len; ++p)
    ok = *p == ' ' || *p == '\t' || *p == '\n' || *p == '\r' || *p == '\f';
  ok = ok && sqlite3_step(stmt) == kDone;
  return (sqlite3_finalize(stmt) == kOk) && ok;
}

// The applied index `_raft_meta` holds: 0 where it holds none, false
// where the table cannot be read or the value is no integer.
bool applied_on_file(sqlite3* db, long long* applied) {
  sqlite3_stmt* stmt = nullptr;
  if (sqlite3_prepare_v2(db,
                         "SELECT v FROM _raft_meta WHERE k='applied_index'",
                         -1, &stmt, nullptr) != kOk ||
      stmt == nullptr)
    return false;
  int rc = sqlite3_step(stmt);
  bool ok = rc == kDone;
  *applied = 0;
  if (rc == kRow && sqlite3_column_type(stmt, 0) == kInteger) {
    *applied = sqlite3_column_int64(stmt, 0);
    ok = sqlite3_step(stmt) == kDone;
  }
  return (sqlite3_finalize(stmt) == kOk) && ok;
}

}  // namespace

extern "C" {

// The address of a symbol of the SQLite this object is bound to.
const void* apply_sqlite_id() {
  return reinterpret_cast<const void*>(&sqlite3_libversion);
}

// The main database's file name as the handle knows it (NULL or "" for
// an in-memory or temporary one), for the borrower's check.
const char* apply_db_filename(sqlite3* db) {
  return sqlite3_db_filename(db, "main");
}

// Apply `n` commands (UTF-8, `lens` bytes each) and, where `meta_index`
// is not 0, the applied index, as one transaction on `db`.  0: committed.
// Non-zero: nothing of it landed and the handle is outside a transaction
// again, except -1: a transaction was already open at the call and was
// not touched.
int apply_txn(sqlite3* db, int n, const char* const* cmds, const int* lens,
              long long meta_index) {
  if (!sqlite3_get_autocommit(db)) return -1;
  bool ok = run(db, "BEGIN");
  int at = 0;
  for (; ok && at < n; ++at) {
    ok = run(db, "SAVEPOINT _apply") && apply_one(db, cmds[at], lens[at]) &&
         run(db, "RELEASE _apply");
  }
  if (ok && meta_index) {
    char meta[160];
    std::snprintf(meta, sizeof meta,
                  "INSERT INTO _raft_meta (k, v) VALUES "
                  "('applied_index', %lld) ON CONFLICT(k) DO UPDATE "
                  "SET v=excluded.v",
                  meta_index);
    ok = run(db, meta);
  }
  ok = ok && run(db, "COMMIT") && sqlite3_get_autocommit(db);
  if (ok) return 0;
  if (!sqlite3_get_autocommit(db)) run(db, "ROLLBACK");
  return at + 1;
}

// A fresh connection's set-up for a machine that reopens its file: 0
// where `db` names the file `expect` (the name an earlier handle of the
// machine was verified by), is outside a transaction, ran `pragmas`, and, where
// `read_meta` is not 0, gave the applied index on file in *applied.
// Non-zero: the first of those that did not hold, and the caller does
// the set-up through the module (the pragmas are idempotent).
int apply_reopen(sqlite3* db, const char* expect, const char* pragmas,
                 int read_meta, long long* applied) {
  const char* name = sqlite3_db_filename(db, "main");
  if (name == nullptr || std::strcmp(name, expect) != 0) return 1;
  if (!sqlite3_get_autocommit(db)) return 2;
  if (!run(db, pragmas)) return 3;
  *applied = 0;
  if (read_meta && !applied_on_file(db, applied)) return 4;
  return 0;
}

// Checkpoint each of `n` handles as `PRAGMA wal_checkpoint(FULL)` does
// (the -wal synced, its pages copied into the file, the file synced),
// `threads` handles at a time.  ok[i] = 1 where that ran to its end:
// SQLITE_OK, and every frame of the log is in the file; secs[i] is the
// time it took.  The caller holds each handle's machine: no statement
// runs on one meanwhile.  A thread that cannot be started leaves its
// share to the others.
void apply_checkpoint_many(sqlite3* const* dbs, int n, int threads, int* ok,
                           double* secs) {
  std::atomic<int> next{0};
  auto work = [&] {
    for (int i; (i = next.fetch_add(1)) < n;) {
      auto t0 = std::chrono::steady_clock::now();
      int in_log = -1, moved = -1;
      int rc = sqlite3_wal_checkpoint_v2(dbs[i], "main", kCheckpointFull,
                                         &in_log, &moved);
      ok[i] = rc == kOk && in_log == moved;
      secs[i] = std::chrono::duration<double>(
                    std::chrono::steady_clock::now() - t0).count();
    }
  };
  std::vector<std::thread> pool;
  for (int t = 1; t < threads && t < n; ++t) {
    try {
      pool.emplace_back(work);
    } catch (const std::system_error&) {
      break;
    }
  }
  work();
  for (auto& t : pool) t.join();
}

}  // extern "C"
