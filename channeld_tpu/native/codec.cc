// Native wire codec for channeld-tpu.
//
// The per-packet hot path — 5-byte tag framing plus snappy compression
// (wire spec: ref pkg/channeld/connection.go:445-541, :683-697) — as a
// CPython extension. The gateway handles every inbound/outbound byte
// through this codec; the Python implementation in protocol/framing.py
// stays as the semantic reference and fallback.
//
// Linked against the system libsnappy via its stable C ABI (snappy-c.h);
// prototypes are declared here because the image ships the library
// without headers.
//
// Build: scripts/build_native.sh  ->  channeld_tpu/native/_codec.*.so

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <fcntl.h>
#include <pthread.h>
#include <signal.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <deque>
#include <exception>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

extern "C" {
// snappy-c.h stable ABI (status: 0 = OK, 1 = INVALID_INPUT, 2 = BUFFER_TOO_SMALL)
int snappy_compress(const char* input, size_t input_length, char* compressed,
                    size_t* compressed_length);
int snappy_uncompress(const char* compressed, size_t compressed_length,
                      char* uncompressed, size_t* uncompressed_length);
size_t snappy_max_compressed_length(size_t source_length);
int snappy_uncompressed_length(const char* compressed, size_t compressed_length,
                               size_t* result);
}

static const unsigned char MAGIC0 = 0x43;  // 'C'
static const unsigned char MAGIC1 = 0x48;  // 'H'
static const size_t HEADER_SIZE = 5;
static const size_t MAX_PACKET_SIZE = 0xFFFF;

static PyObject* CodecError;

// ---- wait accounting -------------------------------------------------------
//
// Where the loop thread's lock-release site (epoll_wait) spends its time:
// the system call itself, read inside the released region, and the
// Py_END_ALLOW_THREADS after it, which is the wait to win the interpreter
// lock back; and the send writer's time in its send calls. Relaxed
// atomics: each is added to by one thread and read by the /metrics
// collector (core/metrics.py), which needs no ordering between them.
// counters() reads them.
static std::atomic<uint64_t> poll_asleep_ns{0}, poll_relock_ns{0}, polls{0};
static std::atomic<uint64_t> pump_write_ns{0};

static inline uint64_t mono_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

static inline void add(std::atomic<uint64_t>& counter, uint64_t amount) {
  counter.fetch_add(amount, std::memory_order_relaxed);
}

// Core frame construction shared by encode_frame, encode_packets and
// send_packets. The size cap applies to the uncompressed payload (matching
// the Python codec and the reference's pre-compression packet cap) so that
// the decoder's decompression cap never rejects an honestly-encoded frame.
//
// frame_payload decides what goes behind the tag: the payload as it is, or
// its snappy form in ``scratch`` where that was asked for and is smaller.
static bool frame_payload(const char** payload, size_t* payload_len,
                          int* compression, std::string& scratch) {
  if (*payload_len > MAX_PACKET_SIZE) {
    PyErr_Format(CodecError, "packet oversized: %zu", *payload_len);
    return false;
  }
  if (*compression == 1) {
    scratch.resize(snappy_max_compressed_length(*payload_len));
    size_t compressed_len = scratch.size();
    if (snappy_compress(*payload, *payload_len, &scratch[0],
                        &compressed_len) == 0 &&
        compressed_len < *payload_len) {
      *payload = scratch.data();
      *payload_len = compressed_len;
    } else {
      // Incompressible (or error): store raw, mirroring the Python codec.
      *compression = 0;
    }
  }
  return true;
}

static void write_tag(unsigned char* dst, size_t payload_len, int compression) {
  dst[0] = MAGIC0;
  dst[1] = MAGIC1;
  dst[2] = (unsigned char)((payload_len >> 8) & 0xFF);
  dst[3] = (unsigned char)(payload_len & 0xFF);
  dst[4] = (unsigned char)compression;
}

static PyObject* build_frame(const char* payload, size_t payload_len,
                             int compression, std::string& scratch) {
  if (!frame_payload(&payload, &payload_len, &compression, scratch))
    return nullptr;
  PyObject* out = PyBytes_FromStringAndSize(nullptr,
                                            (Py_ssize_t)(HEADER_SIZE + payload_len));
  if (out) {
    unsigned char* dst =
        reinterpret_cast<unsigned char*>(PyBytes_AS_STRING(out));
    write_tag(dst, payload_len, compression);
    memcpy(dst + HEADER_SIZE, payload, payload_len);
  }
  return out;
}

// encode_frame(body: bytes, compression: int = 0) -> bytes
static PyObject* codec_encode_frame(PyObject* self, PyObject* args) {
  Py_buffer body;
  int compression = 0;
  if (!PyArg_ParseTuple(args, "y*|i", &body, &compression)) return nullptr;
  std::string scratch;
  PyObject* out = build_frame(static_cast<const char*>(body.buf),
                              (size_t)body.len, compression, scratch);
  PyBuffer_Release(&body);
  return out;
}

// decode_frames(buf: bytes-like) -> (list[tuple[bytes, int]], consumed: int)
//
// Parses every complete frame in buf, decompressing snappy bodies.
// Raises CodecError on a bad magic or zero-size frame (connection-fatal).
static PyObject* codec_decode_frames(PyObject* self, PyObject* args) {
  Py_buffer buf;
  if (!PyArg_ParseTuple(args, "y*", &buf)) return nullptr;

  const unsigned char* data = static_cast<const unsigned char*>(buf.buf);
  size_t len = static_cast<size_t>(buf.len);
  size_t pos = 0;

  PyObject* frames = PyList_New(0);
  if (!frames) {
    PyBuffer_Release(&buf);
    return nullptr;
  }

  while (len - pos >= HEADER_SIZE) {
    const unsigned char* tag = data + pos;
    if (tag[0] != MAGIC0 || tag[1] != MAGIC1) {
      Py_DECREF(frames);
      PyBuffer_Release(&buf);
      PyErr_Format(CodecError, "invalid tag at offset %zu", pos);
      return nullptr;
    }
    size_t size = ((size_t)tag[2] << 8) | (size_t)tag[3];
    if (size == 0) {
      Py_DECREF(frames);
      PyBuffer_Release(&buf);
      PyErr_SetString(CodecError, "zero-size frame");
      return nullptr;
    }
    if (len - pos < HEADER_SIZE + size) break;  // incomplete frame
    int ct = tag[4];
    const char* body = reinterpret_cast<const char*>(tag + HEADER_SIZE);

    PyObject* payload = nullptr;
    if (ct == 1) {
      size_t out_len = 0;
      if (snappy_uncompressed_length(body, size, &out_len) != 0) {
        Py_DECREF(frames);
        PyBuffer_Release(&buf);
        PyErr_SetString(CodecError, "corrupt snappy length preamble");
        return nullptr;
      }
      // Frame bodies are capped at MAX_PACKET_SIZE pre-compression, so a
      // preamble claiming more than a small multiple of that is hostile;
      // allocating it would be a pre-auth memory amplification.
      if (out_len > 4 * MAX_PACKET_SIZE) {
        Py_DECREF(frames);
        PyBuffer_Release(&buf);
        PyErr_Format(CodecError, "snappy uncompressed length %zu exceeds cap",
                     out_len);
        return nullptr;
      }
      payload = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)out_len);
      if (payload &&
          snappy_uncompress(body, size, PyBytes_AS_STRING(payload), &out_len) != 0) {
        Py_DECREF(payload);
        Py_DECREF(frames);
        PyBuffer_Release(&buf);
        PyErr_SetString(CodecError, "corrupt snappy data");
        return nullptr;
      }
    } else {
      payload = PyBytes_FromStringAndSize(body, (Py_ssize_t)size);
    }
    if (!payload) {
      Py_DECREF(frames);
      PyBuffer_Release(&buf);
      return nullptr;
    }
    PyObject* item = Py_BuildValue("(Ni)", payload, ct);
    if (!item || PyList_Append(frames, item) < 0) {
      Py_XDECREF(item);
      Py_DECREF(frames);
      PyBuffer_Release(&buf);
      return nullptr;
    }
    Py_DECREF(item);
    pos += HEADER_SIZE + size;
  }

  PyBuffer_Release(&buf);
  return Py_BuildValue("(Nn)", frames, (Py_ssize_t)pos);
}

// ---- outbound packet building -------------------------------------------
//
// Hand-rolled protobuf wire encoding of chtpu.Packet:
//   Packet.messages    = field 1, length-delimited (tag 0x0A)
//   MessagePack fields = channelId(1)/broadcast(2)/stubId(3)/msgType(4)
//                        varint, msgBody(5) bytes; proto3 zero-omission.
// Byte-identical to the generated serializer (verified in tests).

static size_t varint_size(uint64_t v) {
  size_t n = 1;
  while (v >= 0x80) {
    v >>= 7;
    n++;
  }
  return n;
}

static void write_varint(std::string& out, uint64_t v) {
  while (v >= 0x80) {
    out.push_back((char)((v & 0x7F) | 0x80));
    v >>= 7;
  }
  out.push_back((char)v);
}

// pack_batch walks msgs, a sequence of (channelId, broadcast, stubId,
// msgType, msgBody), and hands ``emit(body, msgs_in_body)`` each packet
// body as it fills: <= 64KB before compression, a single message over
// that skipped (mirroring Connection.flush's batching + oversize skip).
// The one statement of the batching rule, for encode_packets and
// send_packets. False with a Python error set.
template <class Emit>
static bool pack_batch(PyObject* seq, Emit emit) {
  PyObject* fast = PySequence_Fast(seq, "expected a sequence of messages");
  if (!fast) return false;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);

  std::string body;
  body.reserve(MAX_PACKET_SIZE + 64);
  long body_msgs = 0;
  bool ok = true;

  for (Py_ssize_t i = 0; i < n && ok; i++) {
    PyObject* item = PySequence_Fast_GET_ITEM(fast, i);
    unsigned long ch, bc, stub, mt;
    Py_buffer mb;
    if (!PyArg_ParseTuple(item, "kkkky*", &ch, &bc, &stub, &mt, &mb)) {
      ok = false;
      break;
    }
    // MessagePack submessage payload size.
    size_t pack_size = 0;
    if (ch) pack_size += 1 + varint_size(ch);
    if (bc) pack_size += 1 + varint_size(bc);
    if (stub) pack_size += 1 + varint_size(stub);
    if (mt) pack_size += 1 + varint_size(mt);
    if (mb.len) pack_size += 1 + varint_size((uint64_t)mb.len) + (size_t)mb.len;
    size_t entry_size = 1 + varint_size(pack_size) + pack_size;

    if (entry_size > MAX_PACKET_SIZE) {
      PyBuffer_Release(&mb);
      continue;  // oversized single message: skip (caller logs)
    }
    if (body.size() + entry_size > MAX_PACKET_SIZE && !body.empty()) {
      ok = emit(body, body_msgs);
      body.clear();
      body_msgs = 0;
      if (!ok) {
        PyBuffer_Release(&mb);
        break;
      }
    }
    body.push_back((char)0x0A);  // Packet.messages tag
    write_varint(body, pack_size);
    if (ch) {
      body.push_back((char)0x08);
      write_varint(body, ch);
    }
    if (bc) {
      body.push_back((char)0x10);
      write_varint(body, bc);
    }
    if (stub) {
      body.push_back((char)0x18);
      write_varint(body, stub);
    }
    if (mt) {
      body.push_back((char)0x20);
      write_varint(body, mt);
    }
    if (mb.len) {
      body.push_back((char)0x2A);
      write_varint(body, (uint64_t)mb.len);
      body.append(static_cast<const char*>(mb.buf), (size_t)mb.len);
    }
    body_msgs++;
    PyBuffer_Release(&mb);
  }
  Py_DECREF(fast);
  if (ok && !body.empty()) ok = emit(body, body_msgs);
  return ok;
}

// encode_packets(msgs, compression) -> (list[bytes], list[int])
//
// Batches message packs into framed packets; returns the ready-to-write
// frames plus the number of messages packed into each frame (for exact
// sent-metrics attribution).
static PyObject* codec_encode_packets(PyObject* self, PyObject* args) {
  PyObject* seq;
  int compression = 0;
  if (!PyArg_ParseTuple(args, "O|i", &seq, &compression)) return nullptr;

  PyObject* frames = PyList_New(0);
  PyObject* counts = PyList_New(0);
  std::string scratch;
  bool ok = frames && counts &&
            pack_batch(seq, [&](const std::string& body, long msgs) -> bool {
              PyObject* frame = build_frame(body.data(), body.size(),
                                            compression, scratch);
              if (!frame) return false;
              int rc = PyList_Append(frames, frame);
              Py_DECREF(frame);
              if (rc != 0) return false;
              PyObject* cnt = PyLong_FromLong(msgs);
              if (!cnt) return false;
              rc = PyList_Append(counts, cnt);
              Py_DECREF(cnt);
              return rc == 0;
            });
  if (!ok) {
    Py_XDECREF(frames);
    Py_XDECREF(counts);
    return nullptr;
  }
  return Py_BuildValue("(NN)", frames, counts);
}

// ---- the send writer -------------------------------------------------------
//
// One thread (started on first use) owns every registered TCP peer's
// outbound bytes: the loop thread encodes and hands off, the writer makes
// the send calls (doc/concurrency.md). It never touches a Python object
// and never takes the interpreter lock; what it shares with the loop
// thread is the state below, under ``mu``, which neither side holds
// across a system call but the epoll bookkeeping.
//
// A peer is named by a key the writer hands out, never by its fd, and
// the writer writes to its own dup of the socket: the kernel reuses a
// closed fd's number, and a late buffer of a closed connection must not
// reach the socket that takes it. Each peer has ONE FIFO of buffers, so
// every byte of a connection, whoever handed it off, goes out in the
// order handed off. A send that meets a full socket keeps the rest at
// the head of the FIFO and waits for EPOLLOUT on the writer's own epoll
// set; a send that fails drops the peer's queue, closes the dup and
// leaves (key, errno) for writer_completions().
struct WriterBuf {
  std::string bytes;
  uint64_t handed_ns;  // the hand-off's instant, for send_writer_wait
};

enum { OPEN = 0, RELEASE_DRAIN = 1, RELEASE_DROP = 2 };

struct WriterPeer {
  int fd = -1;                  // the writer's dup; -1 once closed
  std::deque<WriterBuf> fifo;   // bytes not yet taken by the writer
  size_t head_sent = 0;         // bytes of fifo.front() already written
  size_t pending = 0;           // bytes handed off and not yet written
  int err = 0;                  // errno of a failed send (queue dropped)
  bool err_reported = false;    // a completion or a hand-off said so
  bool scheduled = false;       // the writer comes back without a signal
  bool busy = false;            // the writer is writing it, unlocked
  bool polled = false;          // fd is in the writer's epoll set
  int release = OPEN;
};

struct Writer {
  pid_t pid;
  int epfd, evfd;
  std::mutex mu;
  std::unordered_map<uint64_t, WriterPeer> peers;
  std::vector<uint64_t> ready;   // keys to look at; evfd signalled
  std::vector<uint64_t> failed;  // keys whose send failed, unreported
};

// Never freed: the thread outlives the interpreter's finalisation.
static Writer* writer = nullptr;
static uint64_t next_key = 1;  // 0 is the eventfd's epoll tag
static std::mutex writer_start;

// Accounting read by counters(): the writer's send calls, the wait of a
// handed-off buffer for its first byte to go out, sends that left bytes
// unsent, and every peer's bytes not yet written.
static std::atomic<uint64_t> writer_wait_ns{0}, writer_batches{0},
    partial_writes{0}, writer_backlog{0};

static void writer_close(Writer* w, WriterPeer& p) {  // under mu
  if (p.fd >= 0) {
    if (p.polled) epoll_ctl(w->epfd, EPOLL_CTL_DEL, p.fd, nullptr);
    close(p.fd);
  }
  p.fd = -1;
  p.polled = false;
  writer_backlog.fetch_sub(p.pending, std::memory_order_relaxed);
  p.pending = 0;
  p.head_sent = 0;
  p.fifo.clear();
  p.scheduled = false;
}

// Writes what ``key`` has queued until its FIFO is empty, its socket is
// full, or a send fails.
static void writer_service(Writer* w, uint64_t key) {
  std::unique_lock<std::mutex> lock(w->mu);
  auto found = w->peers.find(key);
  if (found == w->peers.end()) return;
  WriterPeer& p = found->second;  // stays put: only erase moves it
  std::deque<WriterBuf> batch;
  size_t off = 0;
  for (;;) {
    if (p.release == RELEASE_DROP || p.fd < 0) {
      writer_close(w, p);
      if (p.release != OPEN) w->peers.erase(key);
      return;
    }
    if (batch.empty()) {
      if (p.fifo.empty()) {
        p.scheduled = false;
        if (p.release == RELEASE_DRAIN) {
          writer_close(w, p);
          w->peers.erase(key);
        }
        return;
      }
      batch.swap(p.fifo);
      off = p.head_sent;
      p.head_sent = 0;
    }
    p.busy = true;
    int fd = p.fd;
    lock.unlock();

    struct iovec iov[64];
    int count = 0;
    size_t want = 0;
    for (size_t i = 0; i < batch.size() && count < 64; i++) {
      const std::string& bytes = batch[i].bytes;
      size_t from = i == 0 ? off : 0;
      iov[count].iov_base = const_cast<char*>(bytes.data() + from);
      iov[count].iov_len = bytes.size() - from;
      want += bytes.size() - from;
      count++;
    }
    struct msghdr msg;
    memset(&msg, 0, sizeof(msg));
    msg.msg_iov = iov;
    msg.msg_iovlen = (size_t)count;
    uint64_t began = mono_ns();
    ssize_t took = sendmsg(fd, &msg, MSG_DONTWAIT | MSG_NOSIGNAL);
    int err = took < 0 ? errno : 0;
    uint64_t ended = mono_ns();
    add(pump_write_ns, ended - began);
    size_t done = took > 0 ? (size_t)took : 0;
    if (done < want && err != EINTR) add(partial_writes, 1);
    for (size_t left = done; left > 0;) {
      WriterBuf& head = batch.front();
      if (off == 0) {
        add(writer_wait_ns, ended - head.handed_ns);
        add(writer_batches, 1);
      }
      size_t rest = head.bytes.size() - off;
      if (left < rest) {
        off += left;
        break;
      }
      left -= rest;
      off = 0;
      batch.pop_front();
    }

    lock.lock();
    p.busy = false;
    p.pending -= done;
    writer_backlog.fetch_sub(done, std::memory_order_relaxed);
    if (err == EINTR || p.release == RELEASE_DROP) continue;
    if (err == EAGAIN || err == EWOULDBLOCK || (err == 0 && done < want)) {
      // The socket is full: the rest stays at the head of the FIFO, in
      // front of whatever was handed off meanwhile, until EPOLLOUT.
      for (auto r = batch.rbegin(); r != batch.rend(); ++r)
        p.fifo.push_front(std::move(*r));
      p.head_sent = off;
      struct epoll_event ev;
      memset(&ev, 0, sizeof(ev));
      ev.events = EPOLLOUT | EPOLLONESHOT;
      ev.data.u64 = key;
      epoll_ctl(w->epfd, p.polled ? EPOLL_CTL_MOD : EPOLL_CTL_ADD, p.fd, &ev);
      p.polled = true;
      return;  // still scheduled: EPOLLOUT brings the writer back
    }
    if (err) {
      p.err = err;
      writer_close(w, p);
      if (p.release == OPEN)
        w->failed.push_back(key);
      else
        w->peers.erase(key);
      return;
    }
  }
}

static void writer_main(Writer* w) {
  struct epoll_event events[64];
  std::vector<uint64_t> work;
  for (;;) {
    int n = epoll_wait(w->epfd, events, 64, -1);
    if (n < 0) continue;  // EINTR
    work.clear();
    for (int i = 0; i < n; i++) {
      if (events[i].data.u64 == 0) {
        uint64_t signals;
        ssize_t got = read(w->evfd, &signals, sizeof(signals));
        (void)got;
      } else {
        work.push_back(events[i].data.u64);
      }
    }
    {
      std::lock_guard<std::mutex> guard(w->mu);
      work.insert(work.end(), w->ready.begin(), w->ready.end());
      w->ready.clear();
    }
    for (uint64_t key : work) writer_service(w, key);
  }
}

// The writer of this process, started on first use (again in a child
// after fork: the thread does not follow it). Null with a Python error
// set where it cannot start. Under writer_start.
static Writer* writer_started() {
  if (writer && writer->pid == getpid()) return writer;
  int epfd = epoll_create1(EPOLL_CLOEXEC);
  int evfd = eventfd(0, EFD_CLOEXEC | EFD_NONBLOCK);
  struct epoll_event ev;
  memset(&ev, 0, sizeof(ev));
  ev.events = EPOLLIN;
  ev.data.u64 = 0;
  if (epfd < 0 || evfd < 0 || epoll_ctl(epfd, EPOLL_CTL_ADD, evfd, &ev) < 0) {
    PyErr_SetFromErrno(PyExc_OSError);
    if (epfd >= 0) close(epfd);
    if (evfd >= 0) close(evfd);
    return nullptr;
  }
  Writer* w = new Writer();
  w->pid = getpid();
  w->epfd = epfd;
  w->evfd = evfd;
  // Every signal blocked in the writer: the interpreter's handlers run on
  // the main thread, and the thread inherits the mask it is created with.
  sigset_t all, old;
  sigfillset(&all);
  pthread_sigmask(SIG_BLOCK, &all, &old);
  try {
    std::thread(writer_main, w).detach();
  } catch (const std::exception& e) {
    pthread_sigmask(SIG_SETMASK, &old, nullptr);
    PyErr_Format(PyExc_OSError, "send writer: %s", e.what());
    return nullptr;
  }
  pthread_sigmask(SIG_SETMASK, &old, nullptr);
  writer = w;
  return w;
}

// Queues ``bytes`` behind what ``p`` holds; true where the writer must be
// signalled (the ready list was empty). Under mu.
static bool writer_handoff(Writer* w, uint64_t key, WriterPeer& p,
                           std::string&& bytes) {
  size_t n = bytes.size();
  if (!n) return false;
  p.fifo.push_back(WriterBuf{std::move(bytes), mono_ns()});
  p.pending += n;
  writer_backlog.fetch_add(n, std::memory_order_relaxed);
  if (p.scheduled) return false;
  p.scheduled = true;
  w->ready.push_back(key);
  return w->ready.size() == 1;
}

static void writer_signal(Writer* w) {
  uint64_t one = 1;
  ssize_t put = write(w->evfd, &one, sizeof(one));
  (void)put;
}

// The errno a hand-off to ``p`` reports: that of its failed send, which
// writer_completions() then leaves out, or EBADF for a key the writer no
// longer holds; 0 where the hand-off may go. Under mu.
static int writer_refusal(WriterPeer* p) {
  if (!p || p->release != OPEN) return EBADF;
  if (p->err) p->err_reported = true;
  return p->err;
}

static WriterPeer* writer_find(Writer* w, uint64_t key) {  // under mu
  auto found = w->peers.find(key);
  return found == w->peers.end() ? nullptr : &found->second;
}

// writer_register(fd) -> key
static PyObject* codec_writer_register(PyObject* self, PyObject* args) {
  int fd;
  if (!PyArg_ParseTuple(args, "i", &fd)) return nullptr;
  Writer* w;
  uint64_t key;
  {
    std::lock_guard<std::mutex> guard(writer_start);
    w = writer_started();
    if (!w) return nullptr;
    key = next_key++;
  }
  int own = fcntl(fd, F_DUPFD_CLOEXEC, 0);
  if (own < 0) return PyErr_SetFromErrno(PyExc_OSError);
  std::lock_guard<std::mutex> guard(w->mu);
  w->peers[key].fd = own;
  return PyLong_FromUnsignedLongLong(key);
}

// writer_write(key, data) -> errno (0: handed off)
static PyObject* codec_writer_write(PyObject* self, PyObject* args) {
  unsigned long long key;
  Py_buffer data;
  if (!PyArg_ParseTuple(args, "Ky*", &key, &data)) return nullptr;
  std::string bytes(static_cast<const char*>(data.buf), (size_t)data.len);
  PyBuffer_Release(&data);
  Writer* w = writer;
  int err = EBADF;
  bool signal = false;
  if (w) {
    std::lock_guard<std::mutex> guard(w->mu);
    WriterPeer* p = writer_find(w, key);
    err = writer_refusal(p);
    if (!err) signal = writer_handoff(w, key, *p, std::move(bytes));
  }
  if (signal) writer_signal(w);
  return PyLong_FromLong(err);
}

// writer_release(key, drain): drain=True writes what is queued, then
// closes the writer's dup (the peer reads EOF after the last byte);
// drain=False drops the queue and closes it now.
static PyObject* codec_writer_release(PyObject* self, PyObject* args) {
  unsigned long long key;
  int drain;
  if (!PyArg_ParseTuple(args, "Kp", &key, &drain)) return nullptr;
  Writer* w = writer;
  if (!w) Py_RETURN_NONE;
  std::lock_guard<std::mutex> guard(w->mu);
  WriterPeer* p = writer_find(w, key);
  if (!p || p->release != OPEN) Py_RETURN_NONE;
  p->release = drain ? RELEASE_DRAIN : RELEASE_DROP;
  // The writer closes a peer it is writing (after that one send), or one
  // with bytes to drain (after the last).
  if (!p->busy && (!drain || !p->scheduled)) {
    writer_close(w, *p);
    w->peers.erase(key);
  }
  Py_RETURN_NONE;
}

// writer_pending(key) -> bytes handed off and not yet written
static PyObject* codec_writer_pending(PyObject* self, PyObject* args) {
  unsigned long long key;
  if (!PyArg_ParseTuple(args, "K", &key)) return nullptr;
  Writer* w = writer;
  size_t pending = 0;
  if (w) {
    std::lock_guard<std::mutex> guard(w->mu);
    WriterPeer* p = writer_find(w, key);
    if (p) pending = p->pending;
  }
  return PyLong_FromSize_t(pending);
}

// writer_completions() -> [(key, errno)], each failed send reported once
static PyObject* codec_writer_completions(PyObject* self, PyObject* args) {
  std::vector<std::pair<uint64_t, int>> out;
  Writer* w = writer;
  if (w) {
    std::lock_guard<std::mutex> guard(w->mu);
    for (uint64_t key : w->failed) {
      WriterPeer* p = writer_find(w, key);
      if (p && !p->err_reported && p->release == OPEN) {
        p->err_reported = true;
        out.emplace_back(key, p->err);
      }
    }
    w->failed.clear();
  }
  PyObject* list = PyList_New((Py_ssize_t)out.size());
  for (size_t i = 0; list && i < out.size(); i++) {
    PyObject* pair = Py_BuildValue("(Ki)", (unsigned long long)out[i].first,
                                   out[i].second);
    if (!pair) {
      Py_CLEAR(list);
      break;
    }
    PyList_SET_ITEM(list, (Py_ssize_t)i, pair);
  }
  return list;
}

// send_packets(conns, cap=0) -> list
//
// One pass of the send pump (core/server.py _pump_sends). conns: a
// sequence of (key, msgs, compression), one entry a connection the writer
// holds. For each, the packets encode_packets builds for msgs are handed
// to the writer as one buffer at the tail of the connection's FIFO; the
// call returns without a send, and without giving the interpreter lock
// up. A hand-off that would take the bytes the writer holds for the
// connection past ``cap`` (where non-zero) is refused: the send buffer's
// backstop, MAX_SEND_BUFFER. The result holds, in the order given, for a
// connection
//   (packets, nbytes, combined, msgs, err)
// the packets built, their bytes, how many of them hold several messages
// and the messages in them; ``err`` the errno of an earlier send that
// failed, not yet reported, or ENOBUFS for a hand-off past ``cap``
// (nothing handed off; 0 otherwise); or, in the tuple's place, the
// exception its encode raised, nothing handed off.
struct PumpSend {
  uint64_t key = 0;
  std::string wire;
  long packets = 0, combined = 0, msgs = 0;
  int err = 0;
  PyObject* failed = nullptr;  // owned: the encode's exception
};

static PyObject* codec_send_packets(PyObject* self, PyObject* args) {
  PyObject* seq;
  unsigned long long cap = 0;
  if (!PyArg_ParseTuple(args, "O|K", &seq, &cap)) return nullptr;
  PyObject* fast = PySequence_Fast(seq, "send_packets expects a sequence");
  if (!fast) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  std::vector<PumpSend> sends((size_t)n);
  std::string scratch;

  for (Py_ssize_t i = 0; i < n; i++) {
    PumpSend& s = sends[(size_t)i];
    unsigned long long key;
    PyObject* msgs;
    int compression = 0;
    bool ok =
        PyArg_ParseTuple(PySequence_Fast_GET_ITEM(fast, i), "KOi", &key,
                         &msgs, &compression) &&
        pack_batch(msgs, [&](const std::string& body, long n_msgs) -> bool {
          const char* payload = body.data();
          size_t len = body.size();
          int ct = compression;
          if (!frame_payload(&payload, &len, &ct, scratch)) return false;
          unsigned char tag[HEADER_SIZE];
          write_tag(tag, len, ct);
          s.wire.append(reinterpret_cast<const char*>(tag), HEADER_SIZE);
          s.wire.append(payload, len);
          s.packets++;
          if (n_msgs > 1) s.combined++;
          s.msgs += n_msgs;
          return true;
        });
    s.key = key;
    if (ok) continue;
    // Contained to this connection: its exception is its result.
    PyObject *type, *tb;
    PyErr_Fetch(&type, &s.failed, &tb);
    PyErr_NormalizeException(&type, &s.failed, &tb);
    Py_XDECREF(type);
    Py_XDECREF(tb);
    if (!s.failed)
      s.failed = PyObject_CallFunction(PyExc_RuntimeError, "s",
                                       "send_packets: encode failed");
    s.wire.clear();
  }
  Py_DECREF(fast);

  // The hand-off: one lock for the pass, one signal at most.
  std::vector<size_t> sizes((size_t)n);
  Writer* w = writer;
  bool signal = false;
  {
    std::unique_lock<std::mutex> guard;
    if (w) guard = std::unique_lock<std::mutex>(w->mu);
    for (size_t i = 0; i < (size_t)n; i++) {
      PumpSend& s = sends[i];
      sizes[i] = s.wire.size();
      if (s.failed) continue;
      WriterPeer* p = w ? writer_find(w, s.key) : nullptr;
      s.err = writer_refusal(p);
      if (!s.err && cap && p->pending + s.wire.size() > cap) s.err = ENOBUFS;
      if (!s.err && writer_handoff(w, s.key, *p, std::move(s.wire)))
        signal = true;
    }
  }
  if (signal) writer_signal(w);

  PyObject* results = PyList_New(n);
  for (Py_ssize_t i = 0; results && i < n; i++) {
    PumpSend& s = sends[(size_t)i];
    PyObject* result = s.failed;
    s.failed = nullptr;
    if (!result)
      result = Py_BuildValue("(nnlli)", (Py_ssize_t)s.packets,
                             (Py_ssize_t)sizes[(size_t)i], s.combined, s.msgs,
                             s.err);
    if (!result) {
      Py_CLEAR(results);
      break;
    }
    PyList_SET_ITEM(results, i, result);
  }
  for (PumpSend& s : sends) Py_XDECREF(s.failed);
  return results;
}

// epoll_wait(epfd, timeout_ms, maxevents) -> [(fd, events)]
//
// The event loop's wait (core/tracing.py TimedSelector), as
// select.epoll.poll makes it but timed: the system call, read inside the
// released region, is ``asleep``; the Py_END_ALLOW_THREADS after it,
// the wait to win the interpreter lock back from the device worker, is
// ``relock``. A wait a signal cut short runs the signal's handlers and
// raises InterruptedError (the selector returns no events).
static PyObject* codec_epoll_wait(PyObject* self, PyObject* args) {
  int epfd, timeout_ms, maxevents;
  if (!PyArg_ParseTuple(args, "iii", &epfd, &timeout_ms, &maxevents))
    return nullptr;
  if (maxevents < 1) maxevents = 1;
  std::vector<struct epoll_event> events((size_t)maxevents);
  int n, err = 0;
  uint64_t slept, woke;
  Py_BEGIN_ALLOW_THREADS
  slept = mono_ns();
  n = epoll_wait(epfd, events.data(), maxevents, timeout_ms);
  if (n < 0) err = errno;
  woke = mono_ns();
  Py_END_ALLOW_THREADS
  add(poll_relock_ns, mono_ns() - woke);
  add(poll_asleep_ns, woke - slept);
  add(polls, 1);
  if (n < 0) {
    if (err == EINTR && PyErr_CheckSignals() < 0) return nullptr;
    errno = err;
    return PyErr_SetFromErrno(PyExc_OSError);  // EINTR: InterruptedError
  }
  PyObject* out = PyList_New(n);
  for (int i = 0; out && i < n; i++) {
    PyObject* pair = Py_BuildValue("(iI)", events[(size_t)i].data.fd,
                                   (unsigned int)events[(size_t)i].events);
    if (!pair) {
      Py_CLEAR(out);
      break;
    }
    PyList_SET_ITEM(out, i, pair);
  }
  return out;
}

// counters() -> dict of the wait accounting's and the send writer's
// totals, nanoseconds and counts since the library was loaded, and the
// writer's backlog in bytes now.
static PyObject* codec_counters(PyObject* self, PyObject* args) {
  auto get = [](std::atomic<uint64_t>& c) {
    return (unsigned long long)c.load(std::memory_order_relaxed);
  };
  return Py_BuildValue(
      "{sKsKsKsKsKsKsKsK}", "poll_asleep_ns", get(poll_asleep_ns),
      "poll_relock_ns", get(poll_relock_ns), "polls", get(polls),
      "pump_write_ns", get(pump_write_ns), "partial_writes",
      get(partial_writes), "writer_wait_ns", get(writer_wait_ns),
      "writer_batches", get(writer_batches), "writer_backlog",
      get(writer_backlog));
}

// ---- inbound forward fast path ------------------------------------------
//
// parse_forward(body, conn_id, expect_channel, min_user_type)
//   -> None | (entries, counts)
//
// Scans one serialized chtpu.Packet. When EVERY message in it is a plain
// user-space forward (msgType >= min_user_type, broadcast == 0,
// stubId == 0, channelId == expect_channel, payload small enough to
// re-pack), returns the owner-bound send-queue entries with the
// ServerForwardMessage{clientConnId, payload} wrapper already encoded:
//   entries: list[(channelId, 0, 0, msgType, sfm_bytes)]
//   counts:  dict[msgType, n]   (for metrics attribution)
// Any other content — system messages, unknown fields, malformed wire
// data — returns None and the caller takes the full protobuf path. This
// removes the per-message Packet/MessagePack/ServerForwardMessage
// object churn from the gateway's steady-state ingest
// (ref: the reference parses in Go and forwards via the channel
// goroutine, connection.go:547-615 + message.go:66-126; this is the
// same routing decision made in native code).

static bool read_varint(const uint8_t** pp, const uint8_t* end, uint64_t* out) {
  const uint8_t* p = *pp;
  uint64_t v = 0;
  int shift = 0;
  while (p < end && shift < 64) {
    uint8_t b = *p++;
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *pp = p;
      *out = v;
      return true;
    }
    shift += 7;
  }
  return false;
}

static PyObject* codec_parse_forward(PyObject* self, PyObject* args) {
  Py_buffer buf;
  unsigned long conn_id, expect_ch, min_user;
  if (!PyArg_ParseTuple(args, "y*kkk", &buf, &conn_id, &expect_ch, &min_user))
    return nullptr;

  const uint8_t* p = static_cast<const uint8_t*>(buf.buf);
  const uint8_t* end = p + buf.len;
  PyObject* entries = PyList_New(0);
  PyObject* counts = PyDict_New();
  if (!entries || !counts) {
    Py_XDECREF(entries);
    Py_XDECREF(counts);
    PyBuffer_Release(&buf);
    return nullptr;
  }
  bool slow = false, fail = false;
  std::string sfm;

  while (p < end && !slow && !fail) {
    if (*p != 0x0A) {  // not Packet.messages: unknown top-level field
      slow = true;
      break;
    }
    p++;
    uint64_t mlen = 0;
    if (!read_varint(&p, end, &mlen) || mlen > (uint64_t)(end - p)) {
      slow = true;
      break;
    }
    const uint8_t* mend = p + mlen;
    uint64_t ch = 0, bc = 0, stub = 0, mt = 0, plen = 0;
    const uint8_t* payload = nullptr;
    while (p < mend) {
      uint8_t tag = *p++;
      bool ok = true;
      switch (tag) {
        case 0x08: ok = read_varint(&p, mend, &ch); break;
        case 0x10: ok = read_varint(&p, mend, &bc); break;
        case 0x18: ok = read_varint(&p, mend, &stub); break;
        case 0x20: ok = read_varint(&p, mend, &mt); break;
        case 0x2A:
          ok = read_varint(&p, mend, &plen) && plen <= (uint64_t)(mend - p);
          if (ok) {
            payload = p;
            p += plen;
          }
          break;
        default:
          ok = false;
      }
      if (!ok) {
        slow = true;
        break;
      }
    }
    if (slow) break;
    if ((ch | bc | stub | mt) >> 32) {
      // Over-long varints: protobuf truncates these uint32 fields to 32
      // bits (a crafted msgType of 2^32+5 IS system message 5 there) —
      // defer to the protobuf path so both classify identically.
      slow = true;
      break;
    }
    if (p != mend || mt < min_user || bc || stub || ch != expect_ch ||
        plen + 96 > MAX_PACKET_SIZE) {
      // Not a plain forward (or would oversize the outbound pack once
      // wrapped): let the full path handle the whole packet.
      slow = true;
      break;
    }
    sfm.clear();
    if (conn_id) {
      sfm.push_back((char)0x08);
      write_varint(sfm, conn_id);
    }
    if (plen) {
      sfm.push_back((char)0x12);
      write_varint(sfm, plen);
      sfm.append(reinterpret_cast<const char*>(payload), (size_t)plen);
    }
    PyObject* entry = Py_BuildValue("(kkkky#)", expect_ch, 0UL, 0UL,
                                    (unsigned long)mt, sfm.data(),
                                    (Py_ssize_t)sfm.size());
    if (!entry || PyList_Append(entries, entry) < 0) {
      Py_XDECREF(entry);
      fail = true;
      break;
    }
    Py_DECREF(entry);
    PyObject* key = PyLong_FromUnsignedLong((unsigned long)mt);
    if (!key) {
      fail = true;
      break;
    }
    PyObject* prev = PyDict_GetItem(counts, key);  // borrowed
    PyObject* next = PyLong_FromLong(prev ? PyLong_AsLong(prev) + 1 : 1);
    if (!next || PyDict_SetItem(counts, key, next) < 0) {
      Py_DECREF(key);
      Py_XDECREF(next);
      fail = true;
      break;
    }
    Py_DECREF(key);
    Py_DECREF(next);
  }

  PyBuffer_Release(&buf);
  if (fail) {
    Py_DECREF(entries);
    Py_DECREF(counts);
    return nullptr;
  }
  if (slow) {
    Py_DECREF(entries);
    Py_DECREF(counts);
    Py_RETURN_NONE;
  }
  return Py_BuildValue("(NN)", entries, counts);
}

// compress(data: bytes) -> bytes ; uncompress(data: bytes) -> bytes
static PyObject* codec_compress(PyObject* self, PyObject* args) {
  Py_buffer in;
  if (!PyArg_ParseTuple(args, "y*", &in)) return nullptr;
  size_t max_len = snappy_max_compressed_length((size_t)in.len);
  PyObject* out = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)max_len);
  if (!out) {
    PyBuffer_Release(&in);
    return nullptr;
  }
  size_t out_len = max_len;
  int status = snappy_compress(static_cast<const char*>(in.buf), (size_t)in.len,
                               PyBytes_AS_STRING(out), &out_len);
  PyBuffer_Release(&in);
  if (status != 0) {
    Py_DECREF(out);
    PyErr_Format(CodecError, "snappy_compress failed: %d", status);
    return nullptr;
  }
  if (_PyBytes_Resize(&out, (Py_ssize_t)out_len) < 0) return nullptr;
  return out;
}

static PyObject* codec_uncompress(PyObject* self, PyObject* args) {
  Py_buffer in;
  if (!PyArg_ParseTuple(args, "y*", &in)) return nullptr;
  size_t out_len = 0;
  if (snappy_uncompressed_length(static_cast<const char*>(in.buf), (size_t)in.len,
                                 &out_len) != 0) {
    PyBuffer_Release(&in);
    PyErr_SetString(CodecError, "corrupt snappy length preamble");
    return nullptr;
  }
  if (out_len > 4 * MAX_PACKET_SIZE) {
    PyBuffer_Release(&in);
    PyErr_Format(CodecError, "snappy uncompressed length %zu exceeds cap",
                 out_len);
    return nullptr;
  }
  PyObject* out = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)out_len);
  if (!out) {
    PyBuffer_Release(&in);
    return nullptr;
  }
  int status = snappy_uncompress(static_cast<const char*>(in.buf), (size_t)in.len,
                                 PyBytes_AS_STRING(out), &out_len);
  PyBuffer_Release(&in);
  if (status != 0) {
    Py_DECREF(out);
    PyErr_SetString(CodecError, "corrupt snappy data");
    return nullptr;
  }
  return out;
}

static PyMethodDef codec_methods[] = {
    {"encode_frame", codec_encode_frame, METH_VARARGS,
     "encode_frame(body, compression=0) -> framed bytes"},
    {"decode_frames", codec_decode_frames, METH_VARARGS,
     "decode_frames(buf) -> ([(body, compression)], consumed)"},
    {"encode_packets", codec_encode_packets, METH_VARARGS,
     "encode_packets([(chId, bc, stub, mt, body)], compression) -> ([frames], [counts])"},
    {"send_packets", codec_send_packets, METH_VARARGS,
     "send_packets([(key, [(chId, bc, stub, mt, body)], compression)], "
     "cap=0) -> [(packets, nbytes, combined, msgs, err) | exception]"},
    {"writer_register", codec_writer_register, METH_VARARGS,
     "writer_register(fd) -> key: the send writer takes a dup of the socket"},
    {"writer_write", codec_writer_write, METH_VARARGS,
     "writer_write(key, data) -> errno, 0 where handed off"},
    {"writer_release", codec_writer_release, METH_VARARGS,
     "writer_release(key, drain): drain=True writes what is queued, then "
     "closes; drain=False drops it and closes now"},
    {"writer_pending", codec_writer_pending, METH_VARARGS,
     "writer_pending(key) -> bytes handed off and not yet written"},
    {"writer_completions", codec_writer_completions, METH_NOARGS,
     "writer_completions() -> [(key, errno)] of sends that failed"},
    {"parse_forward", codec_parse_forward, METH_VARARGS,
     "parse_forward(body, conn_id, expect_channel, min_user_type) -> "
     "None | (entries, counts)"},
    {"epoll_wait", codec_epoll_wait, METH_VARARGS,
     "epoll_wait(epfd, timeout_ms, maxevents) -> [(fd, events)], timed"},
    {"counters", codec_counters, METH_NOARGS,
     "counters() -> {poll_asleep_ns, poll_relock_ns, polls, pump_write_ns, "
     "partial_writes, writer_wait_ns, writer_batches, writer_backlog}"},
    {"compress", codec_compress, METH_VARARGS, "snappy compress"},
    {"uncompress", codec_uncompress, METH_VARARGS, "snappy uncompress"},
    {nullptr, nullptr, 0, nullptr},
};

static struct PyModuleDef codec_module = {
    PyModuleDef_HEAD_INIT, "_codec",
    "Native wire codec (framing + snappy) for channeld-tpu.", -1,
    codec_methods,
};

PyMODINIT_FUNC PyInit__codec(void) {
  PyObject* m = PyModule_Create(&codec_module);
  if (!m) return nullptr;
  CodecError = PyErr_NewException("channeld_tpu.native._codec.CodecError",
                                  PyExc_ValueError, nullptr);
  Py_INCREF(CodecError);
  if (PyModule_AddObject(m, "CodecError", CodecError) < 0) {
    Py_DECREF(CodecError);
    Py_DECREF(m);
    return nullptr;
  }
  return m;
}
