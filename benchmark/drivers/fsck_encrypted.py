"""Traffic driver `fsck_encrypted`: driver `fsck`'s scrub over a volume
encrypted at rest (`format --encrypt-rsa-key`, the configuration's
`deployment.encryption`). Its window, clock, meta server, index fill and
bookkeeping are `drivers/fsck.py`'s; what differs is the volume and the check.

`prepare()` writes the volume's RSA key, a PEM with no passphrase, into the
workdir (`lib/envelope_spec.py`), starts the meta server, then the builder of
`lib/volume_encrypted.py`. A program that has no typed error for an object
whose envelope does not open ends the run there: exit 1, no result line.

`check()` is driver `fsck`'s, with three limits more or in place, each 0:
`envelopes_wrong` (stored block objects that do not open under the plain
reference, `lib/envelope_spec.py`, to the plan's bytes), `plaintext_stored`
(stored objects that are their planned block, or begin with its first 4 KiB)
and `tamper_missed` in place of `bitrot_missed`: after the window, outside the
clock, the same seeded byte of one stored object is flipped and one more
scrub has to exit 1, report that block as `corrupt` and no other as broken,
write no digest for it, and retry no GET (`juicefs_object_request_retries`
gains 0). The byte is put back.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time

import numpy as np

from benchmark.drivers import fsck
from benchmark.lib import envelope_spec, volume_encrypted
from benchmark.lib.plan import block_bytes

KEY_FILE = "volume-key.pem"
PLAINTEXT_PREFIX = 4096
RETRIES = "juicefs_object_request_retries"


def _retries() -> float:
    """Object requests the program has retried, all methods."""
    from juicefs_tpu.metric import global_registry

    return sum(float(line.rpartition(" ")[2])
               for line in global_registry().render().splitlines()
               if line.startswith(RETRIES + "{"))


class Driver(fsck.Driver):

    def prepare(self) -> None:
        try:
            from juicefs_tpu.object import CorruptObjectError  # noqa: F401
        except ImportError:
            sys.exit("benchmark: refused: this program has no CorruptObjectError: "
                     "a block whose envelope does not open is retried as a flaky "
                     "store")
        self.key_file = os.path.join(self.workdir, KEY_FILE)
        envelope_spec.make_key(self.key_file,
                               int(self.config["deployment"]["rsa_key_bits"]))
        self._start_server()
        self.builder = volume_encrypted.start(
            self.workdir, self.config, self.seed, self.meta_url, self.key_file)

    def _stored_files(self) -> dict[str, str]:
        """Block key -> the file the store keeps it in, one walk."""
        by_name = {}
        for base, _, files in os.walk(os.path.join(self.workdir, "blob")):
            for name in files:
                by_name[name] = os.path.join(base, name)
        return {key: by_name[os.path.basename(key)] for key in self.block_of}

    def _bitrot_missed(self, want: dict) -> int:
        """The tamper check, where driver `fsck` checks for bitrot: one
        seeded byte of one stored object flipped (the same byte that check
        flips), one more scrub. 1 unless it exits 1 having reported that
        block as corrupt and no other as broken, wrote no digest for it and
        retried no GET. The byte is put back."""
        rng = np.random.default_rng([self.seed, 7])
        key = sorted(self.block_of)[int(rng.integers(len(self.block_of)))]
        at = int(rng.integers(self.block_of[key].size))
        path = self._stored_file(key)

        def flip():
            with open(path, "r+b") as f:
                f.seek(at)
                byte = f.read(1)
                f.seek(at)
                f.write(bytes([byte[0] ^ 0x10]))

        heard = fsck._Heard()
        log = logging.getLogger("cmd.fsck")
        flip()
        retries = _retries()
        log.addHandler(heard)
        try:
            op = self.one_op()
        finally:
            log.removeHandler(heard)
            flip()
        retried = _retries() - retries
        stats = op["stats"]
        with open(op["index_file"]) as f:
            got = json.load(f)
        differing = [k for k in want.keys() | got.keys() if want.get(k) != got.get(k)]
        found = (op["rc"] == 1 and stats is not None
                 and stats.get("corrupt") == 1 and stats["broken"] == 1
                 and stats["mismatches"] == 0 and len(heard.messages) == 1
                 and heard.messages[0].startswith(f"block {key} corrupt: ")
                 and differing == [key] and key not in got and retried == 0)
        self.log(f"tamper: byte {at} of {key} flipped; scrub rc {op['rc']}, "
                 f"reported {heard.messages}, {retried:g} retries")
        return 0 if found else 1

    def _envelopes(self) -> tuple[int, int]:
        """-> (stored block objects that do not open under the volume's key
        to their planned bytes, stored objects that hold their plaintext)."""
        key = envelope_spec.load_key(self.key_file)
        wrong = plain = 0
        for block, path in self._stored_files().items():
            planned = self.block_of[block]
            with open(path, "rb") as f:
                raw = f.read()
            want = block_bytes(self.seed, planned)
            if envelope_spec.open_envelope(key, raw) != want:
                wrong += 1
            if raw == want or (len(want) >= PLAINTEXT_PREFIX
                               and raw.startswith(want[:PLAINTEXT_PREFIX])):
                plain += 1
        return wrong, plain

    def check(self, window: dict, run: dict) -> dict:
        """Driver `fsck`'s check, its bitrot check the tamper check here,
        and what is stored held to the plain reference of the envelope."""
        compared = super().check(window, run)
        compared["tamper_missed"] = compared.pop("bitrot_missed")
        t0 = time.perf_counter()
        wrong, plain = self._envelopes()
        window["reference_s"] += time.perf_counter() - t0
        compared["envelopes_wrong"] = {"value": wrong, "limit": 0}
        compared["plaintext_stored"] = {"value": plain, "limit": 0}
        return compared
