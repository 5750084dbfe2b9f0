// Shared embedded-CPython plumbing for the C ABI libraries
// (predict_api.cc, c_api.cc): one-shot interpreter init, GIL guard,
// thread-local error slot. Mirrors the reference's c_api error contract
// (MXGetLastError returns the last failure on this thread).
#ifndef MXNET_TPU_EMBEDDED_PYTHON_H_
#define MXNET_TPU_EMBEDDED_PYTHON_H_

#include <Python.h>

#include <dlfcn.h>

#include <mutex>
#include <string>

namespace mxtpu {

inline std::string& last_error() {
  thread_local std::string err;
  return err;
}

inline void SetError(const std::string& msg) { last_error() = msg; }

// Record the pending Python exception into the error slot.
inline void SetErrorFromPython() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  std::string msg = "python error";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c) msg = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  SetError(msg);
}

// Ensure an interpreter exists. When loaded into a host C program,
// initialize exactly once; when loaded into a Python process, reuse the
// existing interpreter via GILState.
inline bool EnsurePython() {
  static std::once_flag once;
  static bool ok = true;
  std::call_once(once, []() {
    if (Py_IsInitialized()) return;
    // Hosts that dlopen us with RTLD_LOCAL (Perl's DynaLoader, JNI, …)
    // leave libpython's symbols invisible to CPython extension modules
    // (math.so etc. fail with "undefined symbol: PyFloat_Type").
    // Promote libpython to global visibility before interpreter init;
    // harmless when the host already linked it globally.
    {
      char soname[64];
      snprintf(soname, sizeof(soname), "libpython%d.%d.so.1.0",
               PY_MAJOR_VERSION, PY_MINOR_VERSION);
      if (!dlopen(soname, RTLD_NOW | RTLD_GLOBAL)) {
        snprintf(soname, sizeof(soname), "libpython%d.%d.so",
                 PY_MAJOR_VERSION, PY_MINOR_VERSION);
        dlopen(soname, RTLD_NOW | RTLD_GLOBAL);   // best effort
      }
    }
    Py_InitializeEx(0);
    if (!Py_IsInitialized()) {
      ok = false;
      return;
    }
    // Release the GIL acquired by Py_Initialize so later
    // PyGILState_Ensure calls work uniformly from any thread.
    PyEval_SaveThread();
  });
  if (!ok) SetError("failed to initialize embedded Python");
  return ok && Py_IsInitialized();
}

class Gil {
 public:
  Gil() : state_(PyGILState_Ensure()) {}
  ~Gil() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

}  // namespace mxtpu

#endif  // MXNET_TPU_EMBEDDED_PYTHON_H_
