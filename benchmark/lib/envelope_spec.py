"""The plain reference for a volume encrypted at rest with the default
algorithm `aes256gcm-rsa` (JuiceFS docs, Security > Data Encryption): what a
stored block object has to be. Imports nothing of the program.

An envelope is a 4-byte big-endian length, the data key wrapped by RSA-OAEP
(MGF1 and hash SHA-256, no label) under the volume's key, a 12-byte nonce,
then the body sealed by AES-256-GCM with its 16-byte tag at the end.
"""

from __future__ import annotations

import struct

from cryptography.exceptions import InvalidTag
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import padding, rsa
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

NONCE = 12
TAG = 16
OAEP = padding.OAEP(mgf=padding.MGF1(algorithm=hashes.SHA256()),
                    algorithm=hashes.SHA256(), label=None)


def make_key(path: str, bits: int) -> None:
    """A new RSA key pair, written as an unencrypted PKCS#8 PEM: what
    `openssl genrsa -out my-priv-key.pem 2048` gives the docs' operator."""
    key = rsa.generate_private_key(public_exponent=65537, key_size=bits)
    with open(path, "wb") as f:
        f.write(key.private_bytes(serialization.Encoding.PEM,
                                  serialization.PrivateFormat.PKCS8,
                                  serialization.NoEncryption()))


def load_key(path: str):
    with open(path, "rb") as f:
        return serialization.load_pem_private_key(f.read(), None)


def open_envelope(key, blob: bytes) -> bytes | None:
    """The plaintext an envelope seals under `key`; None where it does not
    open (a length that does not fit, a key that does not unwrap, a body
    that fails its tag)."""
    if len(blob) < 4:
        return None
    (klen,) = struct.unpack_from(">I", blob)
    if 4 + klen + NONCE + TAG > len(blob):
        return None
    wrapped = blob[4:4 + klen]
    nonce = blob[4 + klen:4 + klen + NONCE]
    try:
        data_key = key.decrypt(wrapped, OAEP)
        return AESGCM(data_key).decrypt(nonce, blob[4 + klen + NONCE:], None)
    except (ValueError, InvalidTag):
        return None
