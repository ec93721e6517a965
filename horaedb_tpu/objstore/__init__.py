"""Object-store abstraction — the durability layer and inter-component "network".

The reference's shared medium is the `object_store` crate's put/get/list/delete/
head API over S3-like storage, with LocalFileSystem as the dev backend
(SURVEY §5.8; reference: src/columnar_storage/src/types.rs:135, used at
storage.rs:193,216 and manifest/mod.rs:139-143,301-315). We keep the same
five-verb contract. All methods are async; LocalStore offloads blocking file IO
to threads so manifest/compaction loops never block the event loop.
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import os
import shutil
import threading
from abc import ABC, abstractmethod
from dataclasses import dataclass

# unique per-attempt suffix stream for LocalStore.put_if_absent sidecars
_ifabsent_seq = itertools.count()

from horaedb_tpu.common.error import HoraeError


@dataclass(frozen=True)
class ObjectMeta:
    """Result of `head` — the subset of metadata the engine uses."""

    path: str
    size: int


class NotFound(HoraeError):
    """Raised by get/head/delete on a missing object (manifest recovery
    distinguishes missing-snapshot from corrupt-snapshot, manifest/mod.rs:336-354)."""


class PreconditionFailed(HoraeError):
    """Raised by put_if_absent when the object already exists — the loser's
    signal in the region-ownership epoch race (storage/fence.py)."""


class ObjectStore(ABC):
    """put/get/list/delete/head over a flat namespace of `/`-separated keys."""

    @abstractmethod
    async def put(self, path: str, data: bytes) -> None: ...

    async def put_if_absent(self, path: str, data: bytes) -> None:
        """Atomic create-if-absent: succeeds exactly once per key across all
        concurrent callers; raises PreconditionFailed if the key exists.
        The primitive behind epoch fencing (S3: `If-None-Match: *`
        conditional PUT; local FS: O_EXCL-style link; memory: dict under
        lock). Stores that cannot provide it must override and raise."""
        raise HoraeError(
            f"{type(self).__name__} does not support conditional puts"
        )

    async def verify_conditional_puts(self, prefix: str) -> None:
        """Prove put_if_absent is actually ENFORCED before anything (epoch
        fencing) stakes correctness on it. Part of the store contract so
        callers invoke it unconditionally — a silently-skipped probe is a
        latent split-brain. Default: no-op, because local/memory stores
        enforce natively in-process (O_EXCL link / dict under lock);
        stores whose enforcement is a REMOTE claim (S3-likes: the far
        endpoint's If-None-Match handling) override with a real probe
        that raises HoraeError on a non-enforcing endpoint."""
        return None

    @abstractmethod
    async def get(self, path: str) -> bytes: ...

    async def get_if_changed(
        self, path: str, etag: "str | None"
    ) -> "tuple[bytes | None, str]":
        """Conditional GET — the cluster watch primitive (HTTP 304 /
        If-None-Match analog). Returns `(data, new_etag)` when the object
        differs from `etag`, `(None, etag)` when unchanged; raises
        NotFound on a missing object like `get`. `etag=None` always
        fetches. The default is an unconditional GET plus a content
        digest compare — correct for every backend; stores with real
        ETags (S3-likes) override so an unchanged probe costs one 304,
        not a transfer. Read replicas tail manifests with this
        (horaedb_tpu/cluster/replica.py)."""
        data = await self.get(path)
        new = "d:" + hashlib.blake2b(data, digest_size=16).hexdigest()
        if etag is not None and new == etag:
            return None, etag
        return data, new

    @abstractmethod
    async def list(self, prefix: str) -> list[ObjectMeta]: ...

    @abstractmethod
    async def delete(self, path: str) -> None: ...

    async def delete_many(self, paths: list[str]) -> list[BaseException | None]:
        """Delete each path; what each delete raised (None: deleted), in
        order, never raising itself: the shape a compaction's clean-up
        wants, ninety files a task of which sixty sidecars never existed.
        A store with a cheaper way than one delete a path overrides."""
        return await asyncio.gather(
            *(self.delete(p) for p in paths), return_exceptions=True)

    @abstractmethod
    async def head(self, path: str) -> ObjectMeta: ...

    # Local filesystem path for readers that need one (parquet mmap); stores
    # without local paths return None and callers fall back to `get` bytes.
    def local_path(self, path: str) -> str | None:
        return None

    async def put_stream(self, path: str, chunks) -> int:
        """Streaming put from an async iterator of bytes chunks. The default
        accumulates then puts (fine for in-memory fakes); stores with real
        backends override to bound memory at chunk granularity."""
        parts = []
        async for c in chunks:
            parts.append(c)
        # the join materializes the whole object — CPU-bound for large
        # SSTs, so it runs off the event loop (J018)
        data = await asyncio.to_thread(b"".join, parts)
        await self.put(path, data)
        return len(data)


class MemStore(ObjectStore):
    """In-memory store for tests (the reference uses tmpdir+LocalFileSystem as
    its fake backend, storage.rs:394-396; we provide both)."""

    def __init__(self) -> None:
        self._objects: dict[str, bytes] = {}
        self._lock = asyncio.Lock()

    async def put(self, path: str, data: bytes) -> None:
        async with self._lock:
            self._objects[path] = bytes(data)

    async def put_if_absent(self, path: str, data: bytes) -> None:
        async with self._lock:
            if path in self._objects:
                raise PreconditionFailed(f"object exists: {path}")
            self._objects[path] = bytes(data)

    async def get(self, path: str) -> bytes:
        try:
            return self._objects[path]
        except KeyError:
            raise NotFound(f"object not found: {path}") from None

    async def list(self, prefix: str) -> list[ObjectMeta]:
        norm = prefix.rstrip("/") + "/" if prefix else ""
        out = [
            ObjectMeta(path=k, size=len(v))
            for k, v in self._objects.items()
            if k.startswith(norm)
        ]
        out.sort(key=lambda m: m.path)
        return out

    async def delete(self, path: str) -> None:
        async with self._lock:
            if self._objects.pop(path, None) is None:
                raise NotFound(f"object not found: {path}")

    async def head(self, path: str) -> ObjectMeta:
        try:
            return ObjectMeta(path=path, size=len(self._objects[path]))
        except KeyError:
            raise NotFound(f"object not found: {path}") from None


class LocalStore(ObjectStore):
    """Object store over a local directory (reference: object_store's
    LocalFileSystem, built in src/server/src/main.rs:122-124)."""

    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    def _fs_path(self, path: str) -> str:
        p = os.path.normpath(os.path.join(self.root, path.lstrip("/")))
        if p != self.root and not p.startswith(self.root + os.sep):
            raise HoraeError(f"path escapes store root: {path}")
        return p

    async def put(self, path: str, data: bytes) -> None:
        def _put() -> None:
            fs = self._fs_path(path)
            os.makedirs(os.path.dirname(fs), exist_ok=True)
            # Atomic replace: write sidecar then rename, so a crashed put never
            # leaves a truncated snapshot (manifest commit point semantics,
            # manifest/mod.rs:301-307).
            tmp = fs + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, fs)

        await asyncio.to_thread(_put)

    async def put_if_absent(self, path: str, data: bytes) -> None:
        def _put() -> None:
            fs = self._fs_path(path)
            os.makedirs(os.path.dirname(fs), exist_ok=True)
            # full-content atomic create: write a sidecar, then hard-link it
            # to the final name — link(2) fails with EEXIST atomically, and
            # the object can never be observed partially written. The sidecar
            # name must be unique per ATTEMPT (pid alone collides across the
            # thread pool's concurrent callers racing one key)
            tmp = fs + f".{os.getpid()}.{threading.get_ident()}.{next(_ifabsent_seq)}.ifabsent"
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            try:
                os.link(tmp, fs)
            except FileExistsError:
                raise PreconditionFailed(f"object exists: {path}") from None
            finally:
                try:
                    os.remove(tmp)
                except OSError:
                    pass

        await asyncio.to_thread(_put)

    async def put_stream(self, path: str, chunks) -> int:
        """Streaming put from an async iterator of bytes chunks (the
        multipart-upload analog: the reference streams SST encodes straight
        to the store via AsyncArrowWriter, storage.rs:192-224). Atomic: the
        object appears only after the final rename; an aborted stream leaves
        nothing at `path`. Returns total bytes written."""
        fs = self._fs_path(path)
        os.makedirs(os.path.dirname(fs), exist_ok=True)
        tmp = fs + ".tmp"
        total = 0
        f = await asyncio.to_thread(open, tmp, "wb")
        try:
            async for chunk in chunks:
                await asyncio.to_thread(f.write, chunk)
                total += len(chunk)
            await asyncio.to_thread(f.flush)
            await asyncio.to_thread(os.fsync, f.fileno())
            await asyncio.to_thread(f.close)
            await asyncio.to_thread(os.replace, tmp, fs)
        except BaseException:
            try:
                f.close()
            finally:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
            raise
        return total

    async def get(self, path: str) -> bytes:
        def _get() -> bytes:
            fs = self._fs_path(path)
            try:
                with open(fs, "rb") as f:
                    return f.read()
            except FileNotFoundError:
                raise NotFound(f"object not found: {path}") from None

        return await asyncio.to_thread(_get)

    async def get_if_changed(
        self, path: str, etag: "str | None"
    ) -> "tuple[bytes | None, str]":
        """Stat-token conditional GET: (inode, mtime_ns, size) names the
        object version — every put lands via os.replace, so a changed
        object is a NEW inode. An unchanged probe costs one stat, no
        read (the watch-loop economy the base digest default can't give
        a filesystem store)."""
        def _probe():
            fs = self._fs_path(path)
            try:
                st = os.stat(fs)
            except FileNotFoundError:
                raise NotFound(f"object not found: {path}") from None
            tok = f"s:{st.st_ino}:{st.st_mtime_ns}:{st.st_size}"
            if etag is not None and tok == etag:
                return None, tok
            try:
                with open(fs, "rb") as f:
                    return f.read(), tok
            except FileNotFoundError:
                raise NotFound(f"object not found: {path}") from None

        return await asyncio.to_thread(_probe)

    async def list(self, prefix: str) -> list[ObjectMeta]:
        def _list() -> list[ObjectMeta]:
            base = self._fs_path(prefix) if prefix else self.root
            out: list[ObjectMeta] = []
            if not os.path.isdir(base):
                return out
            for dirpath, _dirnames, filenames in os.walk(base):
                for name in filenames:
                    if name.endswith((".tmp", ".ifabsent")):
                        continue
                    fs = os.path.join(dirpath, name)
                    rel = os.path.relpath(fs, self.root).replace(os.sep, "/")
                    out.append(ObjectMeta(path=rel, size=os.path.getsize(fs)))
            out.sort(key=lambda m: m.path)
            return out

        return await asyncio.to_thread(_list)

    async def delete(self, path: str) -> None:
        def _delete() -> None:
            try:
                os.remove(self._fs_path(path))
            except FileNotFoundError:
                raise NotFound(f"object not found: {path}") from None

        await asyncio.to_thread(_delete)

    async def delete_many(self, paths: list[str]) -> list[BaseException | None]:
        """All of them in ONE thread hop: a hop and its wake-up of the
        event loop cost more than an unlink."""
        def _delete_all() -> list:
            out: list = []
            for path in paths:
                try:
                    os.remove(self._fs_path(path))
                    out.append(None)
                except FileNotFoundError:
                    out.append(NotFound(f"object not found: {path}"))
                except OSError as e:
                    out.append(e)
            return out

        return await asyncio.to_thread(_delete_all)

    async def head(self, path: str) -> ObjectMeta:
        def _head() -> ObjectMeta:
            try:
                return ObjectMeta(path=path, size=os.path.getsize(self._fs_path(path)))
            except FileNotFoundError:
                raise NotFound(f"object not found: {path}") from None

        return await asyncio.to_thread(_head)

    def local_path(self, path: str) -> str | None:
        return self._fs_path(path)

    def destroy(self) -> None:
        shutil.rmtree(self.root, ignore_errors=True)
