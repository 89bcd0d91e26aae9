"""Frame sources — the ``{auto_source}`` resolution layer.

The reference resolves ``{auto_source}`` per request to urisourcebin /
webcam / GigE / appsrc elements feeding decodebin (SURVEY.md §2b
"Template expansion"; request ``source.type`` values uri / webcam /
gige / application). Here each source yields decoded BGR uint8 frames
with nanosecond PTS — decode runs on host CPU (cv2/FFmpeg), the TPU
engine consumes batches downstream.
"""

from __future__ import annotations

import queue
import time
from dataclasses import dataclass
from typing import Iterator, Protocol

import numpy as np

from evam_tpu.obs import get_logger

log = get_logger("media.source")

NS = 1_000_000_000


@dataclass
class FrameEvent:
    """One decoded frame (or audio chunk) entering the pipeline."""

    frame: np.ndarray | None  # BGR uint8 [H, W, 3]; None for audio
    pts_ns: int        # presentation timestamp, ns (reference meta
                       # 'timestamp' field is ns — charts/README.md:117)
    seq: int
    audio: np.ndarray | None = None  # S16LE mono 16 kHz chunk
    #: host decode cost in seconds (set by DecodeWorker) — becomes the
    #: frame trace's "decode" span (obs/trace.py)
    decode_s: float | None = None
    #: when a paced (``realtime``) source's own clock says the frame is
    #: due (``perf_counter``); the runner's feed minus this is
    #: evam_source_lag_seconds
    due_t: float | None = None


class VideoSource(Protocol):
    def frames(self) -> Iterator[FrameEvent]: ...
    def close(self) -> None: ...


class FileSource:
    """File / RTSP / HTTP source via OpenCV (FFmpeg-backed).

    Counterpart of uridecodebin/decodebin in every reference template
    (e.g. pipelines/object_detection/person/pipeline.json:4).
    """

    def __init__(self, uri: str, loop: bool = False, realtime: bool = False):
        self.uri = uri
        self.loop = loop
        self.realtime = realtime
        self._cap = None
        self._closed = False

    def _open(self):
        import cv2

        path = self.uri
        for prefix in ("file://",):
            if path.startswith(prefix):
                path = path[len(prefix):]
        cap = cv2.VideoCapture(path)
        if not cap.isOpened():
            raise IOError(f"cannot open source {self.uri}")
        return cap

    def frames(self) -> Iterator[FrameEvent]:
        self._cap = self._open()
        fps = self._cap.get(5) or 30.0  # CAP_PROP_FPS
        if fps <= 0 or fps > 1000:
            fps = 30.0
        frame_ns = int(NS / fps)
        seq = 0
        t_wall = time.perf_counter()
        while not self._closed:
            ok, frame = self._cap.read()
            if not ok:
                if self.loop and not self._closed:
                    self._cap.release()
                    self._cap = self._open()
                    continue
                break
            yield FrameEvent(frame=frame, pts_ns=seq * frame_ns, seq=seq,
                             due_t=t_wall if self.realtime else None)
            seq += 1
            if self.realtime:
                t_wall += 1.0 / fps
                delay = t_wall - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
        if self._cap is not None:
            self._cap.release()

    def close(self) -> None:
        self._closed = True


class SyntheticSource:
    """Deterministic generated stream (``synthetic://`` URIs).

    Replaces the reference's sample videos (resources/*.mp4, absent
    from this environment — .MISSING_LARGE_BLOBS) for tests and load
    benchmarks: a moving bright square on a dark background, cheap to
    generate at any resolution/fps.
    """

    def __init__(
        self,
        width: int = 768,
        height: int = 432,
        fps: float = 30.0,
        count: int | None = None,
        realtime: bool = False,
        seed: int = 0,
    ):
        self.width, self.height, self.fps = width, height, fps
        self.count = count
        self.realtime = realtime
        self.seed = seed
        self._closed = False

    @classmethod
    def from_uri(cls, uri: str, realtime: bool = False) -> "SyntheticSource":
        # synthetic://640x480@30?count=100&seed=3
        body = uri.split("://", 1)[1]
        params = {}
        if "?" in body:
            body, q = body.split("?", 1)
            params = dict(p.split("=", 1) for p in q.split("&") if "=" in p)
        size, _, fps = body.partition("@")
        w, _, h = size.partition("x")
        return cls(
            width=int(w or 768),
            height=int(h or 432),
            fps=float(fps or 30),
            count=int(params["count"]) if "count" in params else None,
            seed=int(params.get("seed", 0)),
            realtime=realtime,
        )

    def frames(self) -> Iterator[FrameEvent]:
        frame_ns = int(NS / self.fps)
        base = np.full((self.height, self.width, 3), 16, np.uint8)
        sq = max(8, min(self.height, self.width) // 8)
        seq = 0
        t_wall = time.perf_counter()
        while not self._closed and (self.count is None or seq < self.count):
            frame = base.copy()
            x = (self.seed * 37 + seq * 7) % max(1, self.width - sq)
            y = (self.seed * 53 + seq * 5) % max(1, self.height - sq)
            frame[y : y + sq, x : x + sq] = (64, 160, 240)
            yield FrameEvent(frame=frame, pts_ns=seq * frame_ns, seq=seq,
                             due_t=t_wall if self.realtime else None)
            seq += 1
            if self.realtime:
                t_wall += 1.0 / self.fps
                delay = t_wall - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)

    def close(self) -> None:
        self._closed = True


class WebcamSource(FileSource):
    """Live camera source (reference docker/run.sh webcam probe,
    :107-113): cv2 camera index instead of a URI."""

    def __init__(self, index: int = 0):
        super().__init__(uri=str(index), realtime=False)
        self.index = index

    def _open(self):
        import cv2

        cap = cv2.VideoCapture(self.index)
        if not cap.isOpened():
            raise IOError(f"cannot open camera {self.index}")
        return cap


def gige_frame_to_bgr(data: np.ndarray, pixel_format: str) -> np.ndarray:
    """GenICam pixel-format → BGR uint8 (pure helper, unit-testable
    without camera hardware)."""
    import cv2

    fmt = pixel_format.lower()
    if fmt in ("mono8", "mono"):
        return cv2.cvtColor(data, cv2.COLOR_GRAY2BGR)
    if fmt.startswith("bayerrg"):
        return cv2.cvtColor(data, cv2.COLOR_BAYER_RG2BGR)
    if fmt.startswith("bayergb"):
        return cv2.cvtColor(data, cv2.COLOR_BAYER_GB2BGR)
    if fmt.startswith("bayergr"):
        return cv2.cvtColor(data, cv2.COLOR_BAYER_GR2BGR)
    if fmt.startswith("bayerbg"):
        return cv2.cvtColor(data, cv2.COLOR_BAYER_BG2BGR)
    if fmt in ("rgb8", "rgb"):
        return cv2.cvtColor(data, cv2.COLOR_RGB2BGR)
    if fmt in ("bgr8", "bgr"):
        return data
    raise ValueError(f"unsupported GenICam pixel format {pixel_format!r}")


class GigeSource:
    """GenICam / GigE Vision camera source — the gencamsrc counterpart
    (reference resolves ``{auto_source}`` to gencamsrc for gige
    cameras; EII compose wires GENICAM + ``GST_DEBUG gencamsrc``,
    reference eii/docker-compose.yml:59).

    Backends, tried in order:

    1. **harvesters** (GenICam GenTL consumer; needs a ``.cti``
       producer from the camera vendor, path via ``cti`` property or
       ``GENICAM_GENTL64_PATH``);
    2. **cv2 + GStreamer** (``aravissrc``/``gencamsrc`` pipeline
       string) when OpenCV is built with GStreamer.

    Neither ships in this image (no egress), so construction is lazy
    and ``frames()`` raises a clear error naming both options — the
    request contract (``source.type: "gige"`` + serial/pixel-format
    properties) is stable either way.
    """

    def __init__(self, serial: str | None = None,
                 pixel_format: str = "BayerRG8",
                 cti: str | None = None):
        self.serial = serial
        self.pixel_format = pixel_format
        self.cti = cti
        self._ia = None        # harvesters image acquirer
        self._cap = None       # cv2 GStreamer capture
        self._closed = False

    def _open(self) -> None:
        import os as _os

        h = None
        try:
            from harvesters.core import Harvester  # type: ignore

            h = Harvester()
            cti = self.cti or _os.environ.get("GENICAM_GENTL64_PATH")
            if cti:
                for p in cti.split(":"):
                    h.add_file(p)
            h.update()
            self._ia = h.create_image_acquirer(
                serial_number=self.serial) if self.serial else \
                h.create_image_acquirer(0)
            self._harvester = h
            self._ia.start_acquisition()
            return
        except Exception as exc:  # noqa: BLE001 — installed-but-no-device
            # falls through to GStreamer: harvesters without a .cti
            # producer or with no camera raises here, not ImportError
            if h is not None:
                h.reset()
            if not isinstance(exc, ImportError):
                log.info("harvesters backend unavailable: %s", exc)

        import cv2

        if "GStreamer" in cv2.getBuildInformation():
            sel = f"serial={self.serial} " if self.serial else ""
            gst = (
                f"aravissrc {sel}! videoconvert ! "
                "video/x-raw,format=BGR ! appsink"
            )
            cap = cv2.VideoCapture(gst, cv2.CAP_GSTREAMER)
            if cap.isOpened():
                self._cap = cap
                return
        raise RuntimeError(
            "no GigE backend available: install a GenICam GenTL "
            "producer (.cti) + the 'harvesters' package, or an OpenCV "
            "build with GStreamer and aravissrc (reference parity: "
            "gencamsrc in the DL Streamer image)"
        )

    def frames(self) -> Iterator[FrameEvent]:
        self._open()
        seq = 0
        packed = self.pixel_format.lower() in ("rgb8", "rgb", "bgr8", "bgr")
        while not self._closed:
            if self._ia is not None:
                with self._ia.fetch_buffer() as buf:
                    comp = buf.payload.components[0]
                    shape = (
                        (comp.height, comp.width, 3) if packed
                        else (comp.height, comp.width)
                    )
                    # copy INSIDE the with-block: fetch_buffer requeues
                    # the GenTL buffer on exit, so a zero-copy view
                    # would be overwritten by the next capture
                    data = np.array(comp.data.reshape(shape), copy=True)
                frame = gige_frame_to_bgr(data, self.pixel_format)
            else:
                ok, frame = self._cap.read()
                if not ok:
                    break
            yield FrameEvent(frame=frame, pts_ns=time.monotonic_ns(), seq=seq)
            seq += 1

    def close(self) -> None:
        self._closed = True
        if self._ia is not None:
            self._ia.stop_acquisition()
            self._ia.destroy()
            self._harvester.reset()
        if self._cap is not None:
            self._cap.release()


class AppSource:
    """Application-injected frames (appsrc / msgbus-source counterpart,
    reference evas/subscriber.py:96-106 wraps raw bytes into the
    pipeline; here callers push numpy frames or raw BGR bytes)."""

    def __init__(self, maxsize: int = 64):
        self._queue: queue.Queue = queue.Queue(maxsize=maxsize)
        self._closed = False
        self._seq = 0

    def push(self, frame: np.ndarray, pts_ns: int | None = None) -> None:
        """Never blocks: when the consumer stalls (or died), the oldest
        queued frame is dropped — live-stream semantics, and it keeps
        feeder threads (msgbus ingest) and shutdown deadlock-free."""
        if self._closed:
            raise RuntimeError("source closed")
        if pts_ns is None:
            pts_ns = time.monotonic_ns()
        ev = FrameEvent(frame=frame, pts_ns=pts_ns, seq=self._seq)
        while True:
            try:
                self._queue.put_nowait(ev)
                break
            except queue.Full:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    pass
        self._seq += 1

    def push_raw(self, data: bytes, width: int, height: int,
                 pts_ns: int | None = None) -> None:
        frame = np.frombuffer(data, np.uint8).reshape(height, width, 3)
        self.push(frame, pts_ns)

    def end(self) -> None:
        # _closed doubles as the EOS signal: frames() re-checks it on
        # every queue timeout, so EOS delivery cannot be lost even if a
        # concurrent push()'s drop-oldest get_nowait() consumes the
        # in-band None sentinel (the sentinel is only a wake-up
        # accelerator, not the source of truth).
        self._closed = True
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass  # frames() will notice _closed on its next timeout

    def frames(self) -> Iterator[FrameEvent]:
        while True:
            try:
                ev = self._queue.get(timeout=0.2)
            except queue.Empty:
                if self._closed:
                    break
                continue
            if ev is None:
                if self._closed:
                    break
                continue  # stale sentinel displaced by a late push
            yield ev

    def close(self) -> None:
        if not self._closed:
            self.end()


def create_source(source_cfg: dict, realtime: bool = False) -> VideoSource:
    """Resolve a request ``source`` object into a VideoSource.

    Mirrors the reference request schema
    ``{"source": {"uri": ..., "type": "uri"}}``
    (charts/templates/NOTES.txt:9-13).
    """
    stype = source_cfg.get("type", "uri")
    if stype in ("uri", "file"):
        uri = source_cfg["uri"]
        if uri.startswith("synthetic://"):
            return SyntheticSource.from_uri(uri, realtime=realtime)
        if uri.startswith("synthetic-audio://"):
            from evam_tpu.media.audio import SyntheticAudioSource

            return SyntheticAudioSource.from_uri(uri)
        if uri.endswith(".wav"):
            from evam_tpu.media.audio import WavSource

            return WavSource(
                uri,
                loop=bool(source_cfg.get("loop", False)),
                realtime=realtime,
            )
        return FileSource(
            uri,
            loop=bool(source_cfg.get("loop", False)),
            realtime=realtime or bool(source_cfg.get("realtime", False)),
        )
    if stype == "webcam":
        # cv2 needs an int index for camera devices, not a string path
        device = source_cfg.get("device", 0)
        return WebcamSource(int(device))
    if stype == "application":
        return AppSource(maxsize=int(source_cfg.get("queue-size", 64)))
    if stype == "gige":
        # reference {auto_source} resolves gige → gencamsrc
        # (eii/docker-compose.yml:59); properties mirror gencamsrc's
        return GigeSource(
            serial=source_cfg.get("serial"),
            pixel_format=source_cfg.get("pixel-format", "BayerRG8"),
            cti=source_cfg.get("cti"),
        )
    raise ValueError(f"unsupported source type '{stype}'")
