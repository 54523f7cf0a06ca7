"""List/watch cache substrate: ThreadSafeStore/Indexer, FIFO/DeltaFIFO,
ExpirationCache, UndeltaStore, Reflector, Informer.

Reference: pkg/client/cache/ (store.go, index.go, fifo.go,
delta_fifo.go, expiration_cache.go, undelta_store.go,
reflector.go:80-268) and pkg/controller/framework/controller.go
(NewInformer). The Reflector lists, primes its store, then applies
watch deltas; on watch failure it backs off and re-lists — components
therefore tolerate apiserver restarts and compaction (410 Gone)
transparently.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from kubernetes_tpu.server.api import APIError
from kubernetes_tpu.store.watch import ADDED, DELETED, ERROR, MODIFIED
from kubernetes_tpu.utils import sanitizer, tracing


def meta_namespace_key(obj) -> str:
    """Default key func (reference: cache.MetaNamespaceKeyFunc)."""
    if isinstance(obj, dict):
        meta = obj.get("metadata", {})
        ns, name = meta.get("namespace", ""), meta.get("name", "")
    else:
        ns, name = obj.metadata.namespace, obj.metadata.name
    return f"{ns}/{name}" if ns else name


class ThreadSafeStore:
    """Keyed object cache (reference: cache.ThreadSafeStore)."""

    def __init__(self, key_func: Callable = meta_namespace_key):
        self._lock = sanitizer.rlock("informer.store")
        self._items: Dict[str, Any] = {}
        self.key_func = key_func

    def add(self, obj) -> None:
        with self._lock:
            self._items[self.key_func(obj)] = obj

    def update(self, obj) -> None:
        # A real method, not `update = add`: class-time binding would
        # freeze THIS add, bypassing subclass overrides (Indexer would
        # never re-index on MODIFIED events, ExpirationCache never
        # refresh, UndeltaStore never push).
        self.add(obj)

    def delete(self, obj) -> None:
        with self._lock:
            self._items.pop(self.key_func(obj), None)

    def get(self, key: str):
        with self._lock:
            return self._items.get(key)

    def list(self) -> List[Any]:
        with self._lock:
            return list(self._items.values())

    def keys(self) -> List[str]:
        with self._lock:
            return list(self._items.keys())

    def replace(self, objs: List[Any]) -> None:
        with self._lock:
            self._items = {self.key_func(o): o for o in objs}

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


class Indexer(ThreadSafeStore):
    """ThreadSafeStore with named secondary indexes (reference:
    cache.Indexer, index.go). An index func maps an object to a list
    of index values; by_index(name, value) returns every object whose
    func emitted that value — e.g. pods by node, endpoints by service."""

    def __init__(
        self,
        indexers: Optional[Dict[str, Callable[[Any], List[str]]]] = None,
        key_func: Callable = meta_namespace_key,
    ):
        super().__init__(key_func)
        self.indexers = dict(indexers or {})
        # index name -> value -> set of object keys
        self._indices: Dict[str, Dict[str, set]] = {
            name: {} for name in self.indexers
        }
        # Reverse map: key -> [(index name, value), ...] it was indexed
        # under, so unindexing is O(entries for that key) instead of a
        # scan over every bucket of every index (which would serialize
        # readers behind thousands of set.discards per pod update).
        self._indexed_under: Dict[str, List[tuple]] = {}

    def _unindex(self, key: str) -> None:
        for name, value in self._indexed_under.pop(key, ()):
            bucket = self._indices[name].get(value)
            if bucket is not None:
                bucket.discard(key)
                if not bucket:
                    del self._indices[name][value]

    def _index(self, key: str, obj: Any) -> None:
        under = []
        for name, fn in self.indexers.items():
            for value in fn(obj):
                self._indices[name].setdefault(value, set()).add(key)
                under.append((name, value))
        if under:
            self._indexed_under[key] = under

    def add(self, obj) -> None:
        with self._lock:
            key = self.key_func(obj)
            self._unindex(key)
            self._items[key] = obj
            self._index(key, obj)

    def delete(self, obj) -> None:
        with self._lock:
            key = self.key_func(obj)
            self._unindex(key)
            self._items.pop(key, None)

    def replace(self, objs: List[Any]) -> None:
        with self._lock:
            self._items = {self.key_func(o): o for o in objs}
            self._indices = {name: {} for name in self.indexers}
            self._indexed_under = {}
            for key, obj in self._items.items():
                self._index(key, obj)

    def by_index(self, name: str, value: str) -> List[Any]:
        with self._lock:
            keys = self._indices.get(name, {}).get(value, ())
            return [self._items[k] for k in keys if k in self._items]

    def index_values(self, name: str) -> List[str]:
        with self._lock:
            return sorted(
                v for v, keys in self._indices.get(name, {}).items() if keys
            )


class ExpirationCache(ThreadSafeStore):
    """TTL store: entries vanish ttl seconds after their last add
    (reference: cache.ExpirationCache, expiration_cache.go — backs the
    scheduler's assumed-pods window)."""

    def __init__(self, ttl: float, key_func: Callable = meta_namespace_key):
        super().__init__(key_func)
        self.ttl = ttl
        self._stamps: Dict[str, float] = {}

    def _expire_locked(self) -> None:
        now = time.monotonic()
        for key in [k for k, t in self._stamps.items() if now - t > self.ttl]:
            del self._stamps[key]
            self._items.pop(key, None)

    def add(self, obj) -> None:
        with self._lock:
            self._expire_locked()
            key = self.key_func(obj)
            self._items[key] = obj
            self._stamps[key] = time.monotonic()

    def delete(self, obj) -> None:
        with self._lock:
            key = self.key_func(obj)
            self._items.pop(key, None)
            self._stamps.pop(key, None)

    def get(self, key: str):
        with self._lock:
            self._expire_locked()
            return self._items.get(key)

    def list(self) -> List[Any]:
        with self._lock:
            self._expire_locked()
            return list(self._items.values())

    def keys(self) -> List[str]:
        with self._lock:
            self._expire_locked()
            return list(self._items.keys())

    def __len__(self) -> int:
        with self._lock:
            self._expire_locked()
            return len(self._items)


class UndeltaStore(ThreadSafeStore):
    """Store that pushes the FULL current state to a callback on every
    change (reference: cache.UndeltaStore, undelta_store.go — feeds
    consumers that want snapshots, e.g. the proxy's OnUpdate).

    The snapshot is captured AND delivered under the store lock
    (reentrant), so pushes arrive in mutation order and the last push
    always reflects the final state; the callback must not block or
    mutate the store."""

    def __init__(
        self,
        push: Callable[[List[Any]], None],
        key_func: Callable = meta_namespace_key,
    ):
        super().__init__(key_func)
        self.push = push

    def add(self, obj) -> None:
        with self._lock:
            super().add(obj)
            self.push(self.list())

    def delete(self, obj) -> None:
        with self._lock:
            super().delete(obj)
            self.push(self.list())

    def replace(self, objs: List[Any]) -> None:
        with self._lock:
            super().replace(objs)
            self.push(self.list())


class FIFO:
    """Producer/consumer queue with key-dedup: a Pop returns the latest
    version of each enqueued object (reference: cache.FIFO, fifo.go:49-184).

    ``wait_phase`` names a utils/tracing phase that each pop observes:
    the time since the key's first add (an update keeps the stamp, an
    add after the pop — a requeue — stamps anew)."""

    def __init__(
        self,
        key_func: Callable = meta_namespace_key,
        wait_phase: Optional[str] = None,
    ):
        self._lock = sanitizer.lock("informer.fifo")
        self._cond = threading.Condition(self._lock)
        self._items: Dict[str, Any] = {}
        self._queue: List[str] = []
        self._closed = False
        self._wakes: List = []
        self.key_func = key_func
        self._wait_phase = wait_phase
        self._stamps: Dict[str, float] = {}

    def attach_wake(self, event) -> None:
        """Register a threading.Event set whenever the queue gains
        items (or closes). Event-driven consumers (the incremental
        scheduler's micro-ticks) wait on ONE event fed by several
        sources — queue arrivals, watch deltas, commit releases —
        instead of blocking inside pop() where only arrivals can wake
        them. Event.set is async-signal-cheap; no ordering is implied
        beyond 'something changed, sweep the queue'."""
        with self._cond:
            self._wakes.append(event)

    def _signal_locked(self) -> None:
        for ev in self._wakes:
            ev.set()

    def add(self, obj) -> None:
        key = self.key_func(obj)
        with self._cond:
            if key not in self._items:
                self._queue.append(key)
                if self._wait_phase is not None:
                    self._stamps[key] = time.monotonic()
            self._items[key] = obj
            self._cond.notify()
            self._signal_locked()

    update = add

    def delete(self, obj) -> None:
        key = self.key_func(obj)
        with self._cond:
            self._items.pop(key, None)
            self._stamps.pop(key, None)
            # Lazy removal: Pop skips keys without items.

    def pop(self, timeout: Optional[float] = None):
        """Blocking pop (reference: fifo.go:168). None on close/timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                while self._queue:
                    key = self._queue.pop(0)
                    if key in self._items:
                        stamp = self._stamps.pop(key, None)
                        if stamp is not None:
                            tracing.observe_wait(
                                self._wait_phase, time.monotonic() - stamp
                            )
                        return self._items.pop(key)
                if self._closed:
                    return None
                wait = None
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        return None
                self._cond.wait(timeout=wait)

    def replace(self, objs: List[Any]) -> None:
        with self._cond:
            self._items = {self.key_func(o): o for o in objs}
            self._queue = list(self._items.keys())
            if self._wait_phase is not None:
                now = time.monotonic()
                self._stamps = {
                    k: self._stamps.get(k, now) for k in self._items
                }
            self._cond.notify_all()
            self._signal_locked()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            self._signal_locked()

    def __len__(self) -> int:
        with self._lock:
            return len([k for k in self._queue if k in self._items])


class DeltaFIFO:
    """FIFO of per-key DELTA LISTS (reference: cache.DeltaFIFO,
    delta_fifo.go). Unlike FIFO — whose key dedup silently drops
    deletions that race a pending add — a pop returns the ordered
    [(type, object), ...] history for one key since its last pop, so
    consumers observe every transition including Deleted. replace()
    emits Sync deltas and synthesizes Deleted for keys that vanished."""

    SYNC = "SYNC"

    def __init__(self, key_func: Callable = meta_namespace_key):
        self.key_func = key_func
        self._cond = threading.Condition(sanitizer.lock("informer.deltafifo"))
        self._deltas: Dict[str, List[tuple]] = {}
        self._queue: List[str] = []
        self._known: Dict[str, Any] = {}  # last object seen per key
        self._closed = False

    def _append(self, key: str, etype: str, obj: Any) -> None:
        if key not in self._deltas:
            self._deltas[key] = []
            self._queue.append(key)
        self._deltas[key].append((etype, obj))
        self._cond.notify()

    def add(self, obj) -> None:
        with self._cond:
            key = self.key_func(obj)
            etype = MODIFIED if key in self._known else ADDED
            self._known[key] = obj
            self._append(key, etype, obj)

    def update(self, obj) -> None:
        self.add(obj)

    def delete(self, obj) -> None:
        with self._cond:
            key = self.key_func(obj)
            self._known.pop(key, None)
            self._append(key, DELETED, obj)

    def replace(self, objs: List[Any]) -> None:
        with self._cond:
            new = {self.key_func(o): o for o in objs}
            for key, old in list(self._known.items()):
                if key not in new:
                    self._known.pop(key)
                    self._append(key, DELETED, old)
            for key, obj in new.items():
                self._known[key] = obj
                self._append(key, self.SYNC, obj)

    def pop(self, timeout: Optional[float] = None) -> Optional[List[tuple]]:
        """Oldest key's delta list, or None on close/timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                if self._queue:
                    key = self._queue.pop(0)
                    return self._deltas.pop(key)
                if self._closed:
                    return None
                wait = None
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        return None
                self._cond.wait(timeout=wait)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def __len__(self) -> int:
        with self._cond:
            return len(self._queue)


class Reflector:
    """List+watch loop feeding a store (reference: reflector.go:80-268).

    `store` needs add/update/delete/replace. Objects land in wire form
    unless `decode` converts them.
    """

    def __init__(
        self,
        client,
        resource: str,
        store,
        namespace: str = "",
        label_selector: str = "",
        field_selector: str = "",
        decode: Optional[Callable[[dict], Any]] = None,
        resync_period: float = 0.0,
        on_event: Optional[Callable] = None,
        decode_deleted: bool = True,
    ):
        self.client = client
        self.resource = resource
        self.store = store
        self.namespace = namespace
        self.label_selector = label_selector
        self.field_selector = field_selector
        self.decode = decode or (lambda o: o)
        self.resync_period = resync_period
        self.on_event = on_event
        # decode_deleted=False skips the typed decode for DELETED
        # events and hands the raw wire dict to store.delete/on_event:
        # deletions only need the KEY (meta_namespace_key reads dicts),
        # and on high-churn streams the discarded full decode is the
        # reflector thread's main cost. Opt-in — handlers must accept
        # wire dicts for deletes.
        self.decode_deleted = decode_deleted
        self.last_sync_version = 0
        # Monotonic time this reflector last processed a delta or
        # relist — the scheduler daemons' informer-staleness SLI
        # (utils/sli.INFORMER_STALENESS) reads it per solve tick.
        self.last_event_mono = 0.0
        # Watch-resume flag: set once a cycle reaches its watch phase,
        # cleared at each cycle start. When a cycle dies IN the watch
        # (endpoint rotated away, connection reset), the next cycle
        # skips the full re-LIST and resumes the watch from
        # last_sync_version — the new apiserver's watch cache usually
        # still covers it; 410 (compacted/too-old) falls back to LIST.
        self._resume_watch = False
        # Full LISTs issued (the resume regression test's observable).
        self.list_count = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._synced = threading.Event()
        self._stream = None  # in-flight watch; closed by stop()

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "Reflector":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        # Wake the consumer NOW: close() pushes a sentinel, so _consume
        # can block on long waits instead of polling (at 1000 kubelets
        # x 2 informers, a 0.2 s poll interval was 10k thread wakeups/s
        # of pure GIL thrash — the 1000-node drill's biggest cost).
        stream = self._stream
        if stream is not None:
            try:
                stream.close()
            except Exception:
                pass
        if self._thread:
            self._thread.join(timeout=5)

    def wait_for_sync(self, timeout: float = 10.0) -> bool:
        return self._synced.wait(timeout)

    # -- the loop -----------------------------------------------------

    def _run(self) -> None:
        backoff = 0.05
        while not self._stop.is_set():
            try:
                progressed = self._list_and_watch()
            except Exception:
                if self._stop.is_set():
                    return
                time.sleep(backoff)
                backoff = min(backoff * 2, 5.0)
                continue
            if progressed:
                backoff = 0.05
            elif not self._stop.is_set():
                # Idle-close fallback (watcher being shed): the re-list
                # itself must back off too, or a sustained drop storm
                # becomes a full-LIST tight loop against the very
                # server that is shedding us.
                self._stop.wait(backoff)
                backoff = min(backoff * 2, 5.0)

    def _list_and_watch(self) -> bool:
        """One LIST + watch cycle. Returns False only when the watch
        was abandoned after consecutive EMPTY closes (no event ever
        delivered) — _run then backs off before the next re-list."""
        resume = self._resume_watch and self.last_sync_version > 0
        self._resume_watch = False
        if not resume:
            self._list()

        # Consecutive watch closes that delivered NOTHING: the server
        # (or the store's slow-consumer guard, or an injected fault
        # storm) is shedding this watcher. Re-dialing instantly would
        # tight-loop list/watch against a struggling control plane —
        # back off between re-dials and, past the threshold, fall back
        # to a full re-list (return; _run owns that cadence).
        idle_closes = 0
        # From here on a transport failure (the apiserver died, the
        # client rotated endpoints) resumes the WATCH next cycle
        # instead of re-LISTing: the store is synced and
        # last_sync_version tracks every delivered event, so the new
        # replica's watch cache can usually serve the delta directly.
        self._resume_watch = True
        while not self._stop.is_set():
            try:
                stream = self.client.watch(
                    self.resource,
                    namespace=self.namespace,
                    since=self.last_sync_version,
                    label_selector=self.label_selector,
                    field_selector=self.field_selector,
                )
            except APIError as e:
                if e.code == 410:  # compacted/too-old: full re-list
                    self._resume_watch = False
                    return True
                raise
            self._stream = stream
            try:
                delivered = self._consume(stream)
            finally:
                self._stream = None
                stream.close()
            if self._stop.is_set():
                return True
            if delivered:
                idle_closes = 0
                continue
            idle_closes += 1
            if idle_closes >= self._RELIST_AFTER_IDLE_CLOSES:
                # Deliberate fallback: the watch window may be
                # unservable — the next cycle must LIST, not resume.
                self._resume_watch = False
                return False
            self._stop.wait(min(0.05 * (2 ** idle_closes), 2.0))
        return True

    def _list(self) -> None:
        """Full LIST + store replace + synthesized deltas (one half of
        a list/watch cycle; resumed cycles skip it)."""
        # Typed clients return (items, version); raw ones a wire dict.
        items, version = self.client.list(
            self.resource,
            namespace=self.namespace,
            label_selector=self.label_selector,
            field_selector=self.field_selector,
        )
        self.list_count += 1
        objs = [self.decode(o) if isinstance(o, dict) else o for o in items]
        # Objects that vanished during a watch outage must surface as
        # DELETED on relist (DeltaFIFO.replace synthesizes Deleted the
        # same way) — delta subscribers like the incremental scheduler
        # would otherwise carry phantom state forever.
        vanished = []
        if self.on_event is not None and hasattr(self.store, "keys"):
            key_func = getattr(self.store, "key_func", meta_namespace_key)
            new_keys = {key_func(o) for o in objs}
            for k in self.store.keys():
                if k not in new_keys:
                    old = self.store.get(k)
                    if old is not None:
                        vanished.append(old)
        self.store.replace(objs)
        self.last_sync_version = version
        self.last_event_mono = time.monotonic()
        self._synced.set()
        if self.on_event:
            for o in vanished:
                self.on_event(DELETED, o)
            for o in objs:
                self.on_event(ADDED, o)

    #: Empty watch closes tolerated before falling back to a re-list.
    _RELIST_AFTER_IDLE_CLOSES = 3

    def _consume(self, stream) -> int:
        """Drain `stream` until it closes; returns events processed
        (the close-backoff signal above)."""
        delivered = 0
        while not self._stop.is_set():
            # Long block: close() (from stop() or the store dropping a
            # slow consumer) wakes it immediately via the sentinel; the
            # timeout is only a safety net for the stop-vs-register
            # race.
            ev = stream.next(timeout=10.0)
            if ev is None:
                if stream.closed:
                    return delivered  # dropped; outer loop re-establishes
                continue
            if ev.type == ERROR:
                return delivered
            if (
                ev.type == DELETED
                and not self.decode_deleted
                and isinstance(ev.object, dict)
            ):
                obj = ev.object
            elif isinstance(ev.object, dict):
                obj = self.decode(ev.object)
            else:
                obj = ev.object
            if ev.version:
                self.last_sync_version = ev.version
            self.last_event_mono = time.monotonic()
            if ev.type == ADDED:
                self.store.add(obj)
            elif ev.type == MODIFIED:
                self.store.update(obj)
            elif ev.type == DELETED:
                self.store.delete(obj)
            delivered += 1
            if self.on_event:
                self.on_event(ev.type, obj)
        return delivered


class Informer:
    """Reflector + cache + event handlers (reference:
    framework.NewInformer, controller.go:201)."""

    def __init__(
        self,
        client,
        resource: str,
        namespace: str = "",
        label_selector: str = "",
        field_selector: str = "",
        decode: Optional[Callable] = None,
        on_add: Optional[Callable] = None,
        on_update: Optional[Callable] = None,
        on_delete: Optional[Callable] = None,
        decode_deleted: bool = True,
    ):
        self.store = ThreadSafeStore()
        self._on_add = on_add
        self._on_update = on_update
        self._on_delete = on_delete
        self.reflector = Reflector(
            client,
            resource,
            self.store,
            namespace=namespace,
            label_selector=label_selector,
            field_selector=field_selector,
            decode=decode,
            on_event=self._handle,
            decode_deleted=decode_deleted,
        )

    def _handle(self, etype: str, obj) -> None:
        if etype == ADDED and self._on_add:
            self._on_add(obj)
        elif etype == MODIFIED and self._on_update:
            self._on_update(obj)
        elif etype == DELETED and self._on_delete:
            self._on_delete(obj)

    def start(self) -> "Informer":
        self.reflector.start()
        return self

    def stop(self) -> None:
        self.reflector.stop()

    def wait_for_sync(self, timeout: float = 10.0) -> bool:
        return self.reflector.wait_for_sync(timeout)
