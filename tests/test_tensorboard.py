"""Dependency-free TensorBoard writer: round-trip + framing integrity +
(when torch's tensorboard reader is importable) cross-validation against a
real third-party parser."""

import struct

import pytest
from tiny import tiny_cfg

from ddp_classification_pytorch_tpu.utils.tensorboard import (
    SummaryWriter,
    _crc32c,
    read_scalars,
)


def test_crc32c_known_vectors():
    # RFC 3720 / kernel test vectors
    assert _crc32c(b"") == 0
    assert _crc32c(b"123456789") == 0xE3069283
    assert _crc32c(b"\x00" * 32) == 0x8A9136AA


def test_scalar_round_trip(tmp_path):
    w = SummaryWriter(str(tmp_path))
    w.add_scalar("train/loss", 1.5, 0)
    w.add_scalar("train/loss", 0.75, 1)
    w.add_scalar("val/top1", 0.9, 1)
    w.close()
    got = list(read_scalars(w.path))
    assert got == [
        (0, "train/loss", 1.5),
        (1, "train/loss", 0.75),
        (1, "val/top1", pytest.approx(0.9)),
    ]


def test_corruption_detected(tmp_path):
    w = SummaryWriter(str(tmp_path))
    w.add_scalar("x", 1.0, 0)
    w.close()
    data = bytearray(open(w.path, "rb").read())
    data[-6] ^= 0xFF  # flip a payload byte of the last record
    p = tmp_path / "corrupt"
    p.write_bytes(bytes(data))
    with pytest.raises(ValueError, match="corrupt"):
        list(read_scalars(str(p)))


def test_record_framing_layout(tmp_path):
    """First record is the brain.Event:2 version header in TFRecord framing."""
    w = SummaryWriter(str(tmp_path))
    w.close()
    data = open(w.path, "rb").read()
    (length,) = struct.unpack("<Q", data[:8])
    payload = data[12:12 + length]
    assert b"brain.Event:2" in payload
    assert len(data) == 16 + length  # header(8) + crc(4) + payload + crc(4)


def test_third_party_reader_cross_validation(tmp_path):
    """If a real TensorBoard reader is installed, it must parse our files."""
    try:
        from tensorboard.backend.event_processing.event_file_loader import (
            EventFileLoader,
        )
    except ImportError:
        pytest.skip("tensorboard not installed")
    w = SummaryWriter(str(tmp_path))
    w.add_scalar("loss", 2.5, 3)
    w.close()
    events = list(EventFileLoader(w.path).Load())
    scalars = [
        # the loader's data_compat pass migrates simple_value → rank-0 tensor
        (e.step, v.tag,
         v.tensor.float_val[0] if v.HasField("tensor") else v.simple_value)
        for e in events if e.HasField("summary")
        for v in e.summary.value
    ]
    assert scalars == [(3, "loss", 2.5)]


def test_trainer_writes_tb_events(tmp_path):
    from ddp_classification_pytorch_tpu.train.loop import Trainer

    cfg = tiny_cfg("baseline", tmp_path)
    cfg.run.tensorboard = True
    Trainer(cfg).run()
    tb_files = list((tmp_path / "tb").iterdir())
    assert len(tb_files) == 1
    tags = {t for _, t, _ in read_scalars(str(tb_files[0]))}
    assert {"train/loss", "train/top1", "val/val_top1"} <= tags


def test_negative_step_round_trip(tmp_path):
    """int64 two's-complement varint: negative steps must not hang or corrupt."""
    w = SummaryWriter(str(tmp_path))
    w.add_scalar("x", 1.0, -3)
    w.close()
    assert list(read_scalars(w.path)) == [(-3, "x", 1.0)]
