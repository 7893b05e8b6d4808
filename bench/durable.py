"""A durable deployment's medium, as the harness models it.

The program keeps its own account of which files are durable; the
harness takes none of it.  While a durable service runs, ``Medium``
watches the process's ``os.fsync``, ``os.fdatasync`` and ``os.sync``
and keeps, for every file under the service's root, its bytes as of the
last of those calls that covered it.  ``Medium.crash()`` then puts the
root back to exactly that: a file that was synced holds the bytes it was
synced with, and every other file is gone.  That is all a power loss
leaves of a file system that honours ``fsync``.

The model is strict where it cannot see: a file is durable under the
name it had when it was synced (a rename after the sync reads as lost),
and data made durable by ``mmap.flush`` or a file opened ``O_SYNC``
reads as lost too.  An unlink is taken as durable at once.
"""
from __future__ import annotations

import os
import re
import stat
from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple

# the mounts table, and the filesystems on which an fsync persists nothing
MOUNTS = "/proc/mounts"
VOLATILE_FS = ("tmpfs", "ramfs")


def filesystem_type(path, mounts: Optional[str] = None) -> str:
    """The type of the filesystem that holds ``path``, from a
    ``/proc/mounts`` table: the longest mount point that is a prefix of
    the path (the last one listed, where one point is mounted twice)."""
    path = os.path.realpath(path)
    best, kind = -1, "unknown"
    with open(mounts or MOUNTS) as f:
        for line in f:
            fields = line.split()
            if len(fields) < 3:
                continue
            point = re.sub(r"\\([0-7]{3})",
                           lambda m: chr(int(m.group(1), 8)), fields[1])
            inside = (path == point
                      or path.startswith(point.rstrip("/") + "/"))
            if inside and len(point) >= best:
                best, kind = len(point), fields[2]
    return kind


class Medium:
    """The synced bytes of every file under ``root``, kept from
    ``start()`` to ``stop()``."""

    def __init__(self, root):
        self.root = os.path.realpath(root)
        self.synced: Dict[str, bytes] = {}
        self._real = None

    def start(self) -> "Medium":
        fsync, fdatasync, sync = self._real = (os.fsync, os.fdatasync,
                                               os.sync)

        def fsync_(fd):
            fsync(fd)
            self._keep(fd)

        def fdatasync_(fd):
            fdatasync(fd)
            self._keep(fd)

        def sync_():
            sync()
            for path in self._files():
                self._keep_path(path, path)

        os.fsync, os.fdatasync, os.sync = fsync_, fdatasync_, sync_
        return self

    def stop(self) -> None:
        if self._real is not None:
            os.fsync, os.fdatasync, os.sync = self._real
            self._real = None

    def _keep(self, fd) -> None:
        fd = fd if isinstance(fd, int) else fd.fileno()
        st = os.fstat(fd)
        if not stat.S_ISREG(st.st_mode) or st.st_nlink == 0:
            return
        link = f"/proc/self/fd/{fd}"
        path = os.readlink(link)
        if path.startswith(self.root + os.sep):
            self._keep_path(path, link)

    def _keep_path(self, path: str, source: str) -> None:
        with open(source, "rb") as f:
            self.synced[path] = f.read()

    def _files(self) -> Iterator[str]:
        for folder, _dirs, names in os.walk(self.root):
            for name in names:
                path = os.path.join(folder, name)
                if os.path.isfile(path) and not os.path.islink(path):
                    yield path

    def crash(self) -> Tuple[int, int]:
        """Put the root back to what was synced.  Returns the number of
        files put back to their synced bytes and of files dropped."""
        self.stop()
        reverted = dropped = 0
        for path in list(self._files()):
            durable = self.synced.get(path)
            if durable is None:
                os.unlink(path)
                dropped += 1
                continue
            if Path(path).read_bytes() != durable:
                Path(path).write_bytes(durable)
                reverted += 1
        return reverted, dropped
