"""The reference against published known answers, so that a design error
it shared with the program (a wrong constant of hash-to-curve, of the
encoding or of the pairing) could not pass as agreement.

- RFC 9380, appendix J.10 (suite BLS12381G2_XMD:SHA-256_SSWU_RO_):
  hash_to_field here, then the reference's own map to curve, isogeny and
  cofactor clearing, against the published points.
- The Ethereum consensus-spec-tests ``bls/sign`` cases (ciphersuite
  BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_, the one the benchmark signs
  with): public key, signature, and verification through the reference's
  batch verifier, which must also reject a tampered signature.
- The traffic's non-subgroup and infinity signatures: the reference
  rejects both.
"""

import hashlib

import numpy as np
import pytest

from benchmark import twist
from benchmark.reference import native

RFC9380_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"

# msg -> (x_c0, x_c1, y_c0, y_c1) of hash_to_curve(msg)
RFC9380_POINTS = {
    b"": (
        0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
        0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
        0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
        0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6,
    ),
    b"abc": (
        0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
        0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8,
        0x1787327B68159716A37440985269CF584BCB1E621D3A7202BE6EA05C4CFE244AEB197642555A0645FB87BF7466B2BA48,
        0x00AA65DAE3C8D732D10ECD2C50F8A1BAF3001578F71C694E03866E9F3D49AC1E1CE70DD94A733534F106D4CEC0EDDD16,
    ),
    b"abcdef0123456789": (
        0x121982811D2491FDE9BA7ED31EF9CA474F0E1501297F68C298E9F4C0028ADD35AEA8BB83D53C08CFC007C1E005723CD0,
        0x190D119345B94FBD15497BCBA94ECF7DB2CBFD1E1FE7DA034D26CBBA169FB3968288B3FAFB265F9EBD380512A71C3F2C,
        0x05571A0F8D3C08D094576981F4A3B8EDA0A8E771FCDCC8ECCEAF1356A6ACF17574518ACB506E435B639353C2E14827C8,
        0x0BB5E7572275C567462D91807DE765611490205A941A5A6AF3B1691BFE596C31225D3AABDF15FAFF860CB4EF17C7C3BE,
    ),
}

# consensus-spec-tests bls/sign: (sk, message, pubkey, signature)
SIGN_CASES = [
    (0x328388AFF0D4A5B7DC9205ABD374E7E98F3CD9F3418EDB4EAFDA5FB16473D216,
     "ab" * 32,
     "b53d21a4cfd562c469cc81514d4ce5a6b577d8403d32a394dc265dd190b47fa9f829fdd7963afdf972e5e77854051f6f",
     "ae82747ddeefe4fd64cf9cedb9b04ae3e8a43420cd255e3c7cd06a8d88b7c7f8638543719981c5d16fa3527c468c25f0"
     "026704a6951bde891360c7e8d12ddee0559004ccdbe6046b55bae1b257ee97f7cdb955773d7cf29adf3ccbb9975e4eb9"),
    (0x47B8192D77BF871B62E87859D653922725724A5C031AFEABC60BCEF5FF665138,
     "00" * 32,
     "b301803f8b5ac4a1133581fc676dfedc60d891dd5fa99028805e5ea5b08d3491af75d0707adab3b70c6a6a580217bf81",
     "b23c46be3a001c63ca711f87a005c200cc550b9429d5f4eb38d74322144f1b63926da3388979e5321012fb1a0526bcd1"
     "00b5ef5fe72628ce4cd5e904aeaa3279527843fae5ca9ca675f4f51ed8f83bbf7155da9ecc9663100a885d5dc6df96d9"),
]


def hash_to_field(msg: bytes, dst: bytes) -> list:
    """RFC 9380 hash_to_field for Fp2, count 2 (expand_message_xmd, SHA-256)."""
    length = 256
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + length.to_bytes(2, "big") + b"\0"
                        + dst_prime).digest()
    bi = hashlib.sha256(b0 + b"\1" + dst_prime).digest()
    uniform = bi
    for i in range(2, length // 32 + 1):
        bi = hashlib.sha256(bytes(a ^ b for a, b in zip(b0, bi)) + bytes([i])
                            + dst_prime).digest()
        uniform += bi
    e = [int.from_bytes(uniform[64 * k:64 * (k + 1)], "big") % twist.P
         for k in range(4)]
    return [(e[0], e[1]), (e[2], e[3])]


@pytest.mark.parametrize("msg", sorted(RFC9380_POINTS))
def test_hash_to_curve_known_answers(msg):
    u0, u1 = hash_to_field(msg, RFC9380_DST)
    q = twist.add(native.map_to_curve(u0), native.map_to_curve(u1))
    x0, x1, y0, y1 = RFC9380_POINTS[msg]
    assert native.clear_cofactor(q) == twist.compress(((x0, x1), (y0, y1)))


@pytest.mark.parametrize("case", range(len(SIGN_CASES)))
def test_sign_and_verify_known_answers(case):
    sk, msg, pk, sig = SIGN_CASES[case]
    msg, pk, sig = bytes.fromhex(msg), bytes.fromhex(pk), bytes.fromhex(sig)
    assert native.pubkey(sk) == pk
    assert native.g2_mul(native.hash_to_g2(msg), sk) == sig
    raw = native.pubkey_raw(sk)
    assert native.verify_sets([raw], [msg], [sig], [1]) is True
    other = native.g2_mul(native.hash_to_g2(msg), sk + 1)
    assert native.verify_sets([raw], [msg], [other], [1]) is False


def test_rejects_non_subgroup_and_infinity_signatures():
    sk, msg = 12345, b"m" * 32
    raw = native.pubkey_raw(sk)
    sig = native.g2_mul(native.hash_to_g2(msg), sk)
    t = twist.small_order_point(np.random.default_rng(13))
    assert twist.on_curve(t) and twist.mul(t, twist.SMALL_ORDER) is None
    tilted = native.g2_sum([sig, twist.compress(t)])
    assert native.verify_sets([raw], [msg], [sig], [7]) is True
    assert native.verify_sets([raw], [msg], [tilted], [13]) is False
    assert native.verify_sets([raw], [msg], [twist.compress(None)], [1]) is False
