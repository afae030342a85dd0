"""The reference's BLS12-381 (perfbench/reference/bls12_381.py) against
published answers.

The reference is a copy of the program's host BLS code, so a fault that
both share would pass every comparison between them.  These vectors come
from outside both: RFC 9380 (expand_message_xmd, K.1; hash_to_curve for
BLS12381G1_XMD:SHA-256_SSWU_RO_, J.9.1) and the IC BLS signature vectors
of CESS's utils/verify-bls-signatures/tests/tests.rs, which pin the
pairing.
"""

from __future__ import annotations

import pytest

import perfbench_tiny  # noqa: F401  (puts perfbench/ on the path)

from reference import bls12_381 as bls  # noqa: E402

XMD_DST = b"QUUX-V01-CS02-with-expander-SHA256-128"
H2C_DST = b"QUUX-V01-CS02-with-BLS12381G1_XMD:SHA-256_SSWU_RO_"


@pytest.mark.parametrize("msg,out", [
    (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
    (b"abc", "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615"),
])
def test_expand_message_xmd(msg, out):
    assert bls.expand_message_xmd(msg, XMD_DST, 32).hex() == out


@pytest.mark.parametrize("msg,x,y", [
    (b"",
     "052926add2207b76ca4fa57a8734416c8dc95e24501772c814278700eed6d1e4"
     "e8cf62d9c09db0fac349612b759e79a1",
     "08ba738453bfed09cb546dbb0783dbb3a5f1f566ed67bb6be0e8c67e2e81a4cc"
     "68ee29813bb7994998f3eae0c9c6a265"),
    (b"abc",
     "03567bc5ef9c690c2ab2ecdf6a96ef1c139cc0b2f284dca0a9a7943388a49a3a"
     "ee664ba5379a7655d3c68900be2f6903",
     "0b9c15f3fe6e5cf4211f346271d7b01c8f3b28be689c8429c85b67af21553331"
     "1f0b8dfaaa154fa6b88176c229f2885d"),
])
def test_hash_to_g1(msg, x, y):
    p = bls.hash_to_g1(msg, H2C_DST)
    assert (p.x, p.y) == (int(x, 16), int(y, 16))


SIG_A = bytes.fromhex(
    "ace9fcdd9bc977e05d6328f889dc4e7c99114c737a494653cb27a1f55c06f455"
    "5e0f160980af5ead098acc195010b2f7")
MSG_A = bytes.fromhex(
    "0d69632d73746174652d726f6f74e6c01e909b4923345ce5970962bcfe3004bf"
    "d8474a21dae28f50692502f46d90")
KEY_A = bytes.fromhex(
    "814c0e6ec71fab583b08bd81373c255c3c371b2e84863c98a4f1e08b74235d14"
    "fb5d9c0cd546d9685f913a0c0b2cc5341583bf4b4392e467db96d65b9bb4cb71"
    "7112f8472e0d5a4d14505ffd7484b01291091c5f87b98883463f98091a0baaae")
SIG_B = bytes.fromhex(
    "89a2be21b5fa8ac9fab1527e041327ce899d7da971436a1f2165393947b4d942"
    "365bfe5488710e61a619ba48388a21b1")
MSG_B = bytes.fromhex(
    "0d69632d73746174652d726f6f74b294b418b11ebe5dd7dd1dcb099e4e0372b9"
    "a42aef7a7a37fb4f25667d705ea9")
KEY_B = bytes.fromhex(
    "9933e1f89e8a3c4d7fdcccdbd518089e2bd4d8180a261f18d9c247a52768ebce"
    "98dc7328a39814a8f911086a1dd50cbe015e2a53b7bf78b55288893daa15c346"
    "640e8831d72a12bdedd979d28470c34823b8d1c3f4795d9c3984a247132e94fe")
SIG_C = bytes.fromhex(
    "98733cc2b312d5787cd4dba6ea0e19a1f1850b9e8c6d5112f12e12db8e7413a4"
    "ecb4096c23730566c67d9b2694e4e179")
MSG_C = bytes.fromhex(
    "e751fdb69185002b13c8d2954c7d0c39546402ecdde9c2a9a2c6242935"
    "35a5ca2f560a582f705580448fbe1ccdc0e86af3ba4c487a7f73bc9c312556")
KEY_C = bytes.fromhex(
    "87033f48fd8f327ff5d164e85af31433c6a8c73fc5a65bad5d472127205c73c5"
    "168a45e862f5af6d0da5676df45d0a5f1293a530d5498f812a34a280f6bef869"
    "e4ca9b7c275554456d8770733d72ac4006777382fa541873fe002adb12184268")


@pytest.mark.parametrize("sig,msg,key,valid", [
    (SIG_A, MSG_A, KEY_A, True),
    (SIG_B, MSG_B, KEY_B, True),
    (SIG_C, MSG_C, KEY_C, True),
    (SIG_B, MSG_A, KEY_A, False),
    (SIG_A, MSG_B, KEY_B, False),
], ids=["a", "b", "c", "sig_b_on_a", "sig_a_on_b"])
def test_ic_signature_vectors(sig, msg, key, valid):
    assert bls.verify_bls_signature(sig, msg, key) is valid


def test_ic_signature_from_published_key():
    sk = int("6f3977f6051e184b2c412daa1b5c0115ef7ab347cac8d808ffa2c26bd0658243",
             16)
    msg = bytes.fromhex(
        "50484522ad8aede64ec7f86b9273b7ed3940481acf93cdd40a2b77f2be2734a1"
        "4012b2492b6363b12adaeaf055c573e4611b085d2e0fe2153d72453a95eaebf3"
        "50ac3ba6a26ba0bc79f4c0bf5664dfdf5865f69f7fc6b58ba7d068e8")
    assert bls.sign(sk, msg).hex() == (
        "8f7ad830632657f7b3eae17fd4c3d9ff5c13365eea8d33fd0a1a6d8fbebc5152"
        "e066bb0ad61ab64e8a8541c8e3f96de9")
